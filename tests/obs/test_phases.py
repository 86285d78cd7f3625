"""obs/phases: instructions of a compiled module mapped onto named phases."""
import jax
import jax.numpy as jnp

from repro.obs.phases import UNSCOPED, op_phases

PHASES = ("act", "env", "replay_sample", "update")

# a hand-written scheduled module in the compiler's text form: a layout copy
# read only by the gather; a copy-start/copy-done pair read only by the
# update; a copy read by two phases; a scalar with no metadata
HLO = """\
HloModule jit_step, is_scheduled=true

%fused_gather (p0: f32[1000,17], p1: s32[128]) -> f32[128,17] {
  %p0 = f32[1000,17]{0,1} parameter(0)
  %p1 = s32[128]{0} parameter(1)
  ROOT %gather.1 = f32[128,17]{1,0} gather(%p0, %p1), offset_dims={1}, metadata={op_name="jit(step)/while/body/closed_call/replay_sample/gather"}
}

ENTRY %main (buf: f32[1000,17], idx: s32[128], w: f32[17,6]) -> (f32[128,6], f32[128,17], f32[1000,17]) {
  %buf = f32[1000,17]{1,0} parameter(0)
  %idx = s32[128]{0} parameter(1), metadata={op_name="jit(step)/replay_sample/randint"}
  %w = f32[17,6]{1,0} parameter(2)
  %copy.70 = f32[1000,17]{0,1} copy(f32[1000,17]{1,0} %buf)
  %fusion.3 = f32[128,17]{1,0} fusion(f32[1000,17]{0,1} %copy.70, s32[128]{0} %idx), kind=kLoop, calls=%fused_gather, metadata={op_name="jit(step)/while/body/closed_call/replay_sample/gather"}
  %copy-start = (f32[17,6]{0,1}, f32[17,6]{1,0}, u32[]) copy-start(f32[17,6]{1,0} %w)
  %copy-done = f32[17,6]{0,1} copy-done((f32[17,6]{0,1}, f32[17,6]{1,0}, u32[]) %copy-start)
  %fxp_mlp_train_step_critic.4 = f32[128,6]{1,0} custom-call(f32[128,17]{1,0} %fusion.3, f32[17,6]{0,1} %copy-done), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/closed_call/update/cond/branch_1_fun/jit(fxp_mlp_train_step)/fxp_mlp_train_step_critic/pallas_call"}
  %copy.71 = f32[1000,17]{1,0} copy(f32[1000,17]{1,0} %buf)
  %add.1 = f32[1000,17]{1,0} add(f32[1000,17]{1,0} %copy.71, f32[1000,17]{1,0} %copy.71), metadata={op_name="jit(step)/while/body/closed_call/env/add"}
  %zero = f32[] constant(0)
  %reduce.2 = f32[17]{0} reduce(f32[1000,17]{1,0} %copy.71, f32[] %zero), dimensions={0}, metadata={op_name="jit(step)/while/body/closed_call/act/reduce_sum"}
  ROOT %tuple.9 = (f32[128,6]{1,0}, f32[17]{0}, f32[1000,17]{1,0}) tuple(%fxp_mlp_train_step_critic.4, %reduce.2, %add.1)
}
"""


def test_own_scope_is_the_first_phase_component():
    phases = op_phases(HLO, PHASES)
    assert phases["fusion.3"] == "replay_sample"
    assert phases["fxp_mlp_train_step_critic.4"] == "update"
    assert phases["idx"] == "replay_sample"
    assert phases["add.1"] == "env" and phases["reduce.2"] == "act"


def test_a_copy_takes_its_users_phase():
    phases = op_phases(HLO, PHASES)
    assert phases["copy.70"] == "replay_sample"      # its one user is the gather
    # through the asynchronous pair to the launch that reads it
    assert phases["copy-done"] == "update" and phases["copy-start"] == "update"


def test_users_that_disagree_leave_an_op_unscoped():
    phases = op_phases(HLO, PHASES)
    assert phases["copy.71"] == UNSCOPED               # read by env and by act
    assert phases["buf"] == UNSCOPED
    assert phases["tuple.9"] == UNSCOPED and phases["zero"] == "act"


def test_fused_computations_are_read_too():
    # an instruction inside a fused computation keeps its own op_name
    assert op_phases(HLO, PHASES)["gather.1"] == "replay_sample"


def test_phases_name_a_compiled_jax_program():
    def f(x):
        with jax.named_scope("act"):
            y = jnp.tanh(x) * 2.0
        with jax.named_scope("env"):
            return jnp.sin(y) + x

    text = jax.jit(f).lower(jnp.ones((8, 128))).compile().as_text()
    phases = op_phases(text, PHASES)
    assert set(phases.values()) <= {"act", "env", UNSCOPED}
    assert {"act", "env"} & set(phases.values())

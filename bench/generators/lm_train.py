"""LM training cells: the program's LM train step (`repro.train.step`) on
packed documents.

The configuration file holds the model as its published `config.json`
names it (`granitemoehybrid` keys) with the cut the cell runs; the traffic
file gives the sequence length, the batch and the document-length
distribution, the number of steps `correct` follows and the control.  A
run:

  1. makes the start of a run from the seed, in one jitted call: the
     reference's weights (`bench/reference/<reference>.py`), put into the
     program's parameter tree, Adam's zero moments and empty QAT ranges;
  2. set-up: the first step, on the seed's first packed batch; it compiles
     here, or loads from the compile cache.  Adam's first moment after it is
     copied to the host;
  3. measures whole steps, each on the next packed batch and each waited
     for, until `--seconds` have passed: train IPS is sequences (samples)
     per second over the whole span;
  4. frees the program's state and has the reference compute the first
     step from the same weights and batch: the loss, and Adam's first moment
     of its own float32 gradients put on the same Q15.16 lattice and clipped
     to the same global norm, in the order the train step applies them;
  5. holds the SSD to its stated precision on its own: the program's chunked
     scan (`repro.models.mamba2.ssd`) against the reference's token-by-token
     recurrence in float32, on the first Mamba layer's inputs of the first
     batch (the step's moments are dominated by the bfloat16 matmuls, and
     would not see a scan computed a step below float32);
  6. checks the packer's contract on the first and the last timed batch,
     with a few lines of its own: both sides are fed what the packer made.
"""
from __future__ import annotations

import gc
import importlib
import time
from functools import partial

import numpy as np

from bench import harness
from bench import trace as tr
from bench.harness import Outcome, Reading, say

VARIANTS = ("ssd_state_bf16", "no_chunk_carry")


def _period(types: list) -> tuple:
    """The shortest period that tiles the layer types whole."""
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and all(t == types[i % p] for i, t in enumerate(types)):
            return tuple(types[:p])
    return tuple(types)


def model_config(cfg: dict):
    """The program's ModelConfig of a configuration file."""
    from repro.models.config import ATTN_GLOBAL, MAMBA2, ModelConfig

    for key, want in (("model_type", "granitemoehybrid"), ("position_embedding_type", "nope"),
                      ("tie_word_embeddings", True), ("mamba_conv_bias", True),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("num_local_experts", 0), ("hidden_act", "silu")):
        if cfg[key] != want:
            raise ValueError(f"{key} = {cfg[key]!r}: the generator runs {want!r} only")
    layers = cfg["num_hidden_layers"]
    kind = {"mamba": MAMBA2, "attention": ATTN_GLOBAL}
    types = [kind[t] for t in cfg["layer_types"][:layers]]
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    prog = cfg["program"]
    return ModelConfig(
        name=cfg["name"], family="hybrid", n_layers=layers, d_model=d, n_heads=hq,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=d // hq,
        d_ff=cfg["shared_intermediate_size"], vocab_size=cfg["vocab_size"],
        block_pattern=_period(types), mlp_type="glu", act="silu", norm="rmsnorm",
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=True, conv1d_width=cfg["mamba_d_conv"],
        mamba_n_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"], mamba_n_groups=cfg["mamba_n_groups"],
        mamba_chunk=cfg["mamba_chunk_size"], rope=False,
        attn_scale=cfg["attention_multiplier"], embed_scale=float(cfg["embedding_multiplier"]),
        residual_scale=cfg["residual_multiplier"], logits_scaling=float(cfg["logits_scaling"]),
        dtype=prog["dtype"], remat=prog["remat"], qat=cfg["qat"], qat_delay=cfg["qat_delay"],
        qat_bits=cfg["qat_bits"])


def opt_config(cfg: dict):
    from repro.optim import adam

    a = cfg["adam"]
    return adam.AdamConfig(lr=a["lr"], b1=a["b1"], b2=a["b2"], eps=a["eps"],
                           grad_clip_norm=a["grad_clip_norm"])


def reference(cfg: dict):
    return importlib.import_module(f"bench.reference.{cfg['reference']}")


# --------------------------------------------------------------------------- #
# the reference's tree <-> the program's
# --------------------------------------------------------------------------- #

def _slot_of(layer: dict, bt: str) -> dict:
    from repro.models.config import MAMBA2

    mix = ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm", "out_proj") \
        if bt == MAMBA2 else ("wq", "wk", "wv", "wo")
    return {"ln1": {"scale": layer["ln1"]}, "ln2": {"scale": layer["ln2"]},
            "mamba" if bt == MAMBA2 else "attn": {k: layer[k] for k in mix},
            "ffn": {k: layer[k] for k in ("wg", "wu", "wd")}}


def _layer_of(slot: dict) -> dict:
    mix = slot["mamba"] if "mamba" in slot else slot["attn"]
    return dict(ln1=slot["ln1"]["scale"], **mix, ln2=slot["ln2"]["scale"], **slot["ffn"])


def to_program(ref_params: dict, mcfg) -> dict:
    """The reference's weights as the program's parameter tree."""
    import jax
    import jax.numpy as jnp

    p, n = len(mcfg.block_pattern), mcfg.n_periods
    layers = ref_params["layers"]
    scan = [jax.tree.map(lambda *a: jnp.stack(a),
                         *[_slot_of(layers[k * p + i], bt) for k in range(n)])
            for i, bt in enumerate(mcfg.block_pattern)]
    tail = [_slot_of(layers[n * p + i], mcfg.block_pattern[i]) for i in range(mcfg.n_tail)]
    return {"embed": {"embedding": ref_params["embedding"]},
            "final_norm": {"scale": ref_params["final_norm"]}, "frontend": {},
            "scan": scan, "tail": tail}


def to_reference(tree: dict, mcfg) -> dict:
    """A tree in the program's layout (parameters, gradients, moments) in
    the reference's."""
    p, n = len(mcfg.block_pattern), mcfg.n_periods
    layers = [_layer_of({k: _index(v, i) for k, v in tree["scan"][j].items()})
              for i in range(n) for j in range(p)]
    layers += [_layer_of(t) for t in tree["tail"]]
    return dict(embedding=tree["embed"]["embedding"], final_norm=tree["final_norm"]["scale"],
                layers=layers)


def _index(subtree, i):
    if isinstance(subtree, dict):
        return {k: _index(v, i) for k, v in subtree.items()}
    return subtree[i]


# --------------------------------------------------------------------------- #
# the programs a run uses
# --------------------------------------------------------------------------- #

def make_start(cfg: dict):
    """A jitted function of the seed's key that makes the program's start
    from the reference's weights."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    from repro.optim import adam
    from repro.train.step import TrainState

    mcfg = model_config(cfg)
    ref = reference(cfg)

    @jax.jit
    def start(key):
        params = to_program(ref.init(key, cfg), mcfg)
        return TrainState(params=params, opt=adam.init(params), ranges=T.init_ranges(mcfg),
                          step=jnp.zeros((), jnp.int32))

    return start


def step_program(cfg: dict):
    """The jitted train step a run times (its state donated)."""
    import jax

    from repro.train.step import make_train_step

    prog = cfg["program"]
    return jax.jit(make_train_step(model_config(cfg), opt_config(cfg),
                                   attn_chunk=prog["attn_chunk"], ce_chunk=prog["ce_chunk"]),
                   donate_argnums=0)


def data_config(traffic: dict, seed: int):
    from repro.data.synthetic import DataConfig
    from repro.models.config import ShapeConfig

    d = traffic["documents"]
    return (DataConfig(seed=seed, packed=True, doc_len_median=d["median"],
                       doc_len_sigma=d["sigma"], doc_len_min=d["min"], doc_len_max=d["max"]),
            ShapeConfig("train_packed", "train", traffic["seq_len"], traffic["batch"]))


def batch_program(cfg: dict, traffic: dict, seed: int):
    """A jitted function of the step index: the packed batch of that step,
    and its documents and causal query-key pairs within documents."""
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import make_batch

    dcfg, shape = data_config(traffic, seed)
    mcfg = model_config(cfg)

    @jax.jit
    def batch(step):
        b = make_batch(dcfg, mcfg, shape, step)
        seg = b["segment_ids"]
        t = jnp.arange(seg.shape[1])
        first = jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], 1)
        in_doc = t - jax.lax.cummax(jnp.where(first, t, 0), axis=1)
        return b, dict(docs=jnp.sum(seg[:, -1] + 1), pairs=jnp.sum(in_doc + 1))

    return batch


# --------------------------------------------------------------------------- #
# a run
# --------------------------------------------------------------------------- #

def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float, devices,
        compiles) -> Outcome:
    import jax
    import jax.numpy as jnp

    cfg, traffic = cell.config, cell.traffic
    key = jax.random.key(seed)
    state = make_start(cfg)(key)
    step = step_program(cfg)
    batch = batch_program(cfg, traffic, seed)

    # set-up: the first step
    b, _ = batch(jnp.int32(0))
    state, metrics = step(state, b)
    first_loss = float(metrics["loss"])
    setup_s = time.perf_counter() - t_start
    snap = dict(loss=first_loss, step=int(state.step), moment=jax.device_get(state.opt.mu),
                params=jax.device_get(state.params))
    del b
    compiles_before = compiles.count

    log_dir = harness.start_trace(cell.name, seed) if trace else None
    steps, stats = 0, []
    with jax.profiler.TraceAnnotation("bench.traced"):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.step"):
                b, st = batch(jnp.int32(steps + 1))
                state, metrics = step(state, b)
            with jax.profiler.TraceAnnotation("bench.readback"):
                float(metrics["loss"])
            steps += 1
            stats.append(st)
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.count - compiles_before
    mem = harness.memory_peak(devices)
    last_loss = float(metrics["loss"])
    del state, metrics, b
    gc.collect()

    seqs = steps * traffic["batch"]
    docs = sum(int(s["docs"]) for s in stats)
    pairs = sum(int(s["pairs"]) for s in stats)
    notes = dict(compiles_in_window=in_window, steps=steps, span_s=elapsed,
                 tokens_per_s=seqs * traffic["seq_len"] / elapsed,
                 documents_per_sequence=docs / seqs, first_loss=first_loss, last_loss=last_loss)
    checks, numbers = check(cfg, traffic, cell.limits, key, seed, snap, last_step=steps)
    reading = None
    if trace:
        reading = Reading(trace=tr.load_and_remove(log_dir), counters=dict(steps=steps),
                          work=harness.work_module(cfg), config=cfg, traffic=traffic,
                          peaks=harness.device_kind_peaks(devices[0].device_kind),
                          measured=dict(steps=steps, sequences=seqs,
                                        attn_pairs_per_sequence=pairs / seqs),
                          chips=len(devices))
    return Outcome(metrics=dict(setup_s=setup_s, train_ips=seqs / elapsed),
                   attempted=steps, failed=0, checks=checks, memory_peak_bytes=mem,
                   reading=reading, notes=notes, numbers=numbers)


# --------------------------------------------------------------------------- #
# the comparison with the reference
# --------------------------------------------------------------------------- #

def _variant(cfg: dict, name: str):
    ref = reference(cfg)
    if name == "sound":
        return ref.SOUND
    if name == "ssd_state_bf16":
        return ref.Variant(state_dtype="bfloat16")
    if name == "no_chunk_carry":
        return ref.Variant(reset_every=cfg["mamba_chunk_size"])
    raise ValueError(f"unknown variant {name!r}; known: sound, {', '.join(VARIANTS)}")


def reference_step(cfg: dict, traffic: dict, key, seed: int, variant: str) -> dict:
    """The reference's first step from the run's start and first batch: its
    loss, Adam's first moment and the parameters' change, the starting
    weights (all on the host), and the share of gradient elements the
    Q15.16 lattice zeroes."""
    import jax
    import jax.numpy as jnp

    ref = reference(cfg)
    a = cfg["adam"]
    params = jax.jit(partial(ref.init, cfg=cfg))(key)
    b, _ = batch_program(cfg, traffic, seed)(jnp.int32(0))
    loss, grads = ref.loss_and_grads(cfg, _variant(cfg, variant))(params, b)
    moment, zeroed = ref.first_moment(grads, a["b1"], a["grad_clip_norm"], cfg["qat"])
    moment = jax.device_get(moment)     # one tree of the model's size on the device at a time
    change = jax.device_get(ref.first_change(
        params, grads, a["lr"], a["b1"], a["b2"], a["eps"], a["grad_clip_norm"], cfg["qat"]))
    return dict(loss=float(loss), moment=moment, change=change, zeroed=float(zeroed),
                start=jax.device_get(params))


def _leaves(tree: dict) -> list:
    out = [("embedding", tree["embedding"]), ("final_norm", tree["final_norm"])]
    for i, layer in enumerate(tree["layers"]):
        out += [(f"layers.{i}.{k}", v) for k, v in sorted(layer.items())]
    return out


def leaf_gaps(cand: dict, refs: dict) -> tuple[float, float, str]:
    """Each leaf's ‖c − r‖ over the larger of ‖r‖ and the median leaf's;
    the worst leaf's, the median leaf's, and the worst leaf's name."""
    pairs = list(zip(_leaves(cand), _leaves(refs)))
    ref_norms = [float(np.linalg.norm(np.asarray(r, np.float64))) for _, (_, r) in pairs]
    med = float(np.median(ref_norms))
    gaps = [float(np.linalg.norm(np.asarray(c, np.float64) - np.asarray(r, np.float64)))
            / max(n, med) for ((_, c), (_, r)), n in zip(pairs, ref_norms)]
    worst = int(np.argmax(gaps))
    return gaps[worst], float(np.median(gaps)), pairs[worst][0][0]


def compare(cand: dict, refs: dict) -> dict:
    """The numbers `correct` compares, for a candidate (the program's first
    step, or a reference variant in its place) against the reference's:
    the loss's relative gap, and the worst and median leaf's gap of Adam's
    first moment and of the parameters' change (`leaf_gaps`)."""
    out = dict(loss_gap=abs(cand["loss"] - refs["loss"]) / abs(refs["loss"]))
    worst = {}
    for name in ("moment", "change"):
        out[f"{name}_gap"], out[f"{name}_median_gap"], worst[name] = leaf_gaps(
            cand[name], refs[name])
    say(f"bench: worst leaf {worst}")
    return out


def ssd_gap(cfg: dict, traffic: dict, key, seed: int, candidate: str) -> float:
    """‖y − y_ref‖ / ‖y_ref‖ of the first Mamba layer's SSD output (without
    the D skip) on the run's first batch: y_ref the reference's recurrence
    in float32, y the program's chunked scan (`candidate` "program") or a
    reference variant.  Every side gets the same inputs: the layer's x, B
    and C rounded to the program's compute dtype, as its projection emits
    them, and dt in float32."""
    import jax
    import jax.numpy as jnp

    from repro.models import mamba2

    ref = reference(cfg)
    cdt = jnp.dtype(cfg["program"]["dtype"])
    b, _ = batch_program(cfg, traffic, seed)(jnp.int32(0))

    @jax.jit
    def inputs(key, b):
        (x, dt, A, B, C), reset = ref.ssd_inputs(ref.init(key, cfg), b, cfg)
        return (x.astype(cdt), dt, A, B.astype(cdt), C.astype(cdt)), reset

    def recurrence(ins, v):
        up = lambda a: a.astype(jnp.float32)
        x, dt, A, B, C = ins
        with jax.default_matmul_precision("highest"):
            return jax.jit(partial(ref.recurrence, v=v))(up(x), dt, A, up(B), up(C), reset)

    with jax.default_matmul_precision("highest"):
        ins, reset = inputs(key, b)
    y_ref = recurrence(ins, ref.SOUND)
    if candidate == "program":
        y = jax.jit(partial(mamba2.ssd, chunk=cfg["mamba_chunk_size"]))(
            *ins, b["segment_ids"])
    else:
        y = recurrence(ins, _variant(cfg, candidate))
    return float(jnp.linalg.norm(y - y_ref) / jnp.linalg.norm(y_ref))


def packing_faults(b: dict, docs: dict) -> int:
    """The positions of a packed batch that break the packer's contract,
    checked with numpy alone: segment ids start at 0 and rise by 0 or 1;
    a label is -100 exactly where the next token starts a document or the
    row ends, and is the next token elsewhere; every whole document is
    `min`–`max` tokens long, the last (cut) one at most `max`."""
    tok, seg, lab = (np.asarray(b[k]) for k in ("tokens", "segment_ids", "labels"))
    rise = np.diff(seg, axis=1)
    ends = np.concatenate([rise != 0, np.ones_like(seg[:, :1], bool)], axis=1)
    faults = int(np.sum(seg[:, 0] != 0)) + int(np.sum((rise != 0) & (rise != 1)))
    faults += int(np.sum((lab == -100) != ends))
    faults += int(np.sum(~ends[:, :-1] & (lab[:, :-1] != tok[:, 1:])))
    for row in seg:
        lengths = np.bincount(row)
        faults += int(np.sum((lengths[:-1] < docs["min"]) | (lengths[:-1] > docs["max"])))
        faults += int(lengths[-1] > docs["max"])
    return faults


def _packing_faults(cfg: dict, traffic: dict, seed: int, steps) -> float:
    import jax

    batch = batch_program(cfg, traffic, seed)
    return float(sum(packing_faults(jax.device_get(batch(s)[0]), traffic["documents"])
                     for s in steps))


def _tree_sub(a: dict, b: dict) -> dict:
    """a - b leaf by leaf, on the host (reference layout)."""
    sub = lambda x, y: np.asarray(x, np.float32) - np.asarray(y, np.float32)
    return dict(embedding=sub(a["embedding"], b["embedding"]),
                final_norm=sub(a["final_norm"], b["final_norm"]),
                layers=[{k: sub(la[k], lb[k]) for k in lb} for la, lb in zip(a["layers"], b["layers"])])


def check(cfg, traffic, limits, key, seed, snap, last_step: int) -> tuple[list, dict]:
    import jax.numpy as jnp

    mcfg = model_config(cfg)
    refs = reference_step(cfg, traffic, key, seed, "sound")
    change = _tree_sub(to_reference(snap["params"], mcfg), refs["start"])
    cand = dict(loss=snap["loss"], moment=to_reference(snap["moment"], mcfg), change=change)
    nums = compare(cand, refs)
    nums["step_gap"] = float(abs(snap["step"] - traffic["check_steps"]))
    zeroed = refs["zeroed"]
    del refs, change, cand          # the model-sized trees, before the SSD's check
    nums["ssd_gap"] = ssd_gap(cfg, traffic, key, seed, "program")
    nums["packing_faults"] = _packing_faults(cfg, traffic, seed,
                                             (jnp.int32(0), jnp.int32(last_step)))
    nums["lattice_zeroed_share"] = zeroed
    say(f"bench: check numbers {nums}")
    return [(k, nums[k], limits[k]) for k in limits], nums


def readings(cell, seed: int, variant: str) -> dict:
    """The numbers of a reference variant put in the program's place, from
    the same start and batch: `ssd_state_bf16` (the recurrence's state and
    decays in bfloat16, one step below the float32 stated) is the control,
    `no_chunk_carry` (the state dropped at every chunk boundary) the
    planted fault.  For `bench/control.py`."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    key = jax.random.key(seed)
    refs = reference_step(cfg, traffic, key, seed, "sound")
    cand = reference_step(cfg, traffic, key, seed, variant)
    nums = compare(cand, refs)
    nums["step_gap"] = 0.0
    del refs, cand
    nums["ssd_gap"] = ssd_gap(cfg, traffic, key, seed, variant)
    nums["packing_faults"] = _packing_faults(cfg, traffic, seed, (jax.numpy.int32(0),))
    return nums

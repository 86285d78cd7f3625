"""Table II — cross-platform comparison row for FIXAR-on-TPU.

The paper compares FA3C (VCU1525), the PPO accelerator (U200) and FIXAR
(U50) on peak IPS, DSP count, network size, and energy efficiency.  We emit
our platform's row: network size (bytes of the DDPG model), measured CPU
IPS, and the modeled TPU-target numbers from fig10, alongside the paper's
published rows for context.
"""
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import json

from benchmarks.common import RESULTS, emit

from repro.rl import ddpg
from repro.rl.envs.locomotion import make

PAPER_ROWS = {
    "FA3C(ASPLOS19)": {"peak_ips": 2550.0, "ipw": 141.7,
                       "network_kb": 2592.0, "precision": "fp32"},
    "PPO(FCCM20)": {"peak_ips": 15286.8, "ipw": None,
                    "network_kb": 229.6, "precision": "fp32"},
    "FIXAR(U50)": {"peak_ips": 38779.8, "ipw": 2638.0,
                   "network_kb": 514.4, "precision": "fxp32/16"},
}


def network_size_kb(env_name: str = "halfcheetah") -> float:
    import jax
    env = make(env_name)
    st = ddpg.init(jax.random.key(0), env.spec, ddpg.DDPGConfig())
    n = sum(x.size for t in (st.actor, st.critic) for x in jax.tree.leaves(t))
    return n * 4 / 1024  # fxp32 carriers


def main(argv=None):
    kb = network_size_kb()
    rows = dict(PAPER_ROWS)
    fig10 = RESULTS / "fig10_halfcheetah.json"
    ours = {"network_kb": round(kb, 1), "precision": "fxp32/16 (Q15.16+A16)"}
    if fig10.exists():
        data = json.loads(fig10.read_text())
        best = max(data.values(), key=lambda r: r["ips_tpu_modeled"])
        ours.update(peak_ips_tpu_modeled=round(best["ips_tpu_modeled"], 1),
                    ipw_tpu_modeled=round(best["ips_per_w_tpu_modeled"], 1))
    rows["FIXAR(TPUv5e,ours)"] = ours
    emit("tableii/network_kb", 0.0, f"ours_kb={kb:.1f};paper_kb=514.4")
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "tableii.json").write_text(json.dumps(rows, indent=2))
    for k, v in rows.items():
        print(f"# {k}: {v}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

"""Adaptive dispatch — the paper's configurable PE array as a cost model.

FIXAR's AAP core runs ONE array under two dataflows and flips per workload
shape: intra-layer parallelism when a single vector must finish fast
(inference), intra-batch parallelism when many independent MVMs amortize the
array (training).  The serving engine faces the same choice per micro-batch,
plus a pure-XLA reference fallback:

  mode     kernel                       parallelism    launches
  ------   --------------------------   ------------   -----------------
  fused    kernels/fxp_mlp (1 launch)   intra-batch    1 (whole network)
  layer    kernels/fxp_matmul chain     intra-layer    L (one per layer)
  jnp      plain XLA matmuls            none (ref)     1 fused XLA call

The dispatcher scores each mode with a two-term affine cost

    t(mode, B) = launches(mode) * per_launch_us[mode]
               + B * kflops_per_item * us_per_kflop[mode]

and picks the argmin.  Launch counts and FLOP shapes come from the kernels'
own cost hints (`fused_cost_hint` / `chain_cost_hint`), so the model tracks
the kernels if their structure changes.  The default coefficients encode
the hardware-shaped regime (fused pays a big single-launch setup for the
best per-item rate; the per-layer chain is the cheapest way to finish one
vector); `CostModel.from_bench` refits the model from measured
`BENCH_fused_mlp.json` acting-path IPS — with the two-batch
`actor_ips_by_batch` measurements it separates slope (per-item rate) from
intercept (launch overhead), which is what `benchmarks/serve_bench`
consumes.

The dispatcher prices the act phase only: training runs the one update
`ddpg.DDPGConfig.backend` names, so the learner has no choice to make.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Optional, Sequence

from repro.kernels._compat import mlp_flops as flops_per_item
from repro.kernels.fxp_matmul.ops import chain_cost_hint
from repro.kernels.fxp_mlp.ops import fused_cost_hint

MODES = ("fused", "layer", "jnp")

# maps BENCH_fused_mlp.json's actor_ips keys to a mode
BACKEND_TO_MODE = {"pallas": "fused", "pallas_layer": "layer", "jnp": "jnp"}


def cost_hint(mode: str, dims: Sequence[int]) -> dict:
    """The per-mode launch/FLOP shape of one act: the two kernel modes
    describe themselves (`fused_cost_hint` / `chain_cost_hint`); the jnp
    fallback is one fused XLA dispatch over the same MLP."""
    if mode == "fused":
        return fused_cost_hint(dims)
    if mode == "layer":
        return chain_cost_hint(dims)
    if mode == "jnp":
        return {"launches": 1, "flops_per_item": flops_per_item(dims),
                "parallelism": "none"}
    raise ValueError(f"unknown serve mode {mode!r}; expected one of {MODES}")


@dataclasses.dataclass(frozen=True)
class ModeCost:
    per_launch_us: float   # fixed cost per kernel launch
    us_per_kflop: float    # marginal cost per item-kFLOP


# Hardware-shaped defaults (see module docstring).  With the paper actor
# (17-400-300-6, ~257 kFLOP/item) these cross over at B ~ 100:
#   B=1   -> layer (3 cheap launches beat one big fused setup)
#   B=512 -> fused (per-item rate dominates, batch rides the grid axis)
DEFAULT_COSTS = {
    "fused": ModeCost(per_launch_us=120.0, us_per_kflop=0.0010),
    "layer": ModeCost(per_launch_us=10.0, us_per_kflop=0.0045),
    "jnp": ModeCost(per_launch_us=45.0, us_per_kflop=0.0120),
}


@dataclasses.dataclass
class CostModel:
    """Per-mode affine latency model + argmin chooser."""

    costs: dict[str, ModeCost]
    source: str = "default"

    @staticmethod
    def default() -> "CostModel":
        return CostModel(dict(DEFAULT_COSTS))

    @staticmethod
    def launches(mode: str, dims: Sequence[int]) -> int:
        return cost_hint(mode, dims)["launches"]

    def estimate_us(self, mode: str, batch: int, dims: Sequence[int]) -> float:
        c = self.costs[mode]
        hint = cost_hint(mode, dims)
        kflops = batch * hint["flops_per_item"] / 1e3
        return c.per_launch_us * hint["launches"] + c.us_per_kflop * kflops

    def choose(self, batch: int, dims: Sequence[int],
               modes: Optional[Sequence[str]] = None) -> str:
        return min(MODES if modes is None else modes,
                   key=lambda m: self.estimate_us(m, batch, dims))

    @staticmethod
    def _fit_mode(mode: str, net: Sequence[int], by_batch: dict,
                  single_us: Optional[float], single_batch: int,
                  base: ModeCost) -> Optional[ModeCost]:
        """One mode's affine fit from measured throughput.

        Preferred input: `by_batch` — {batch: items-per-second} at TWO (or
        more) batch sizes.  Two measurements separate the slope from the
        intercept of `t(B) = launches*per_launch + B*kflops*rate`: the
        extreme-batch pair gives `slope = (t2-t1)/(B2-B1)` (the per-item
        rate) and `intercept = t1 - slope*B1` (the launch overhead), so
        BOTH coefficients are fitted instead of only the marginal rate.

        Fallback: a single measured wall time `single_us` for a batch of
        `single_batch` items — keep `base`'s launch overhead and back out
        the marginal rate.  Returns None when nothing usable was measured.
        """
        hint = cost_hint(mode, net)
        kflops = hint["flops_per_item"] / 1e3

        # ---- two-point fit: slope AND intercept ---------------------------
        points = sorted((int(b), int(b) / float(v) * 1e6)
                        for b, v in dict(by_batch).items() if float(v) > 0)
        if len(points) >= 2 and points[0][0] != points[-1][0]:
            (b1, t1), (b2, t2) = points[0], points[-1]
            slope = (t2 - t1) / (b2 - b1)
            intercept = t1 - slope * b1
            if slope > 0 and intercept > 0:
                return ModeCost(per_launch_us=intercept / hint["launches"],
                                us_per_kflop=slope / kflops)
            # degenerate fit (noise gave a negative coefficient): fall
            # through to single-point

        # ---- legacy single-point: rate only, `base` overheads -------------
        if single_us is None or single_us <= 0:
            return None
        overhead = base.per_launch_us * hint["launches"]
        marginal_us = max(single_us - overhead, 0.1 * single_us)
        return ModeCost(base.per_launch_us,
                        marginal_us / (single_batch * kflops))

    @staticmethod
    def from_bench(path, fallback_to_default: bool = True) -> "CostModel":
        """Recalibrate the affine cost model from `BENCH_fused_mlp.json`:
        fits from `actor_ips_by_batch` (two-point, both coefficients) or
        the legacy single-batch `actor_ips` (rate only, default overheads)
        — see `_fit_mode`.

        Missing file / missing modes / degenerate fits keep their defaults
        (the model must stay total — the dispatcher cannot refuse to
        answer).
        """
        path = pathlib.Path(path)
        costs = dict(DEFAULT_COSTS)
        if not path.exists():
            if not fallback_to_default:
                raise FileNotFoundError(path)
            return CostModel(costs, source="default (no bench file)")
        try:
            data = json.loads(path.read_text())
            b0 = int(data.get("config", {}).get("batch", 256))
            net = list(data.get("config", {}).get("net", [17, 400, 300, 6]))
            by_batch = data.get("actor_ips_by_batch", {})
            single = data.get("actor_ips", {})
            for backend in sorted({*single, *by_batch}):
                mode = BACKEND_TO_MODE.get(backend)
                if mode is None:
                    continue
                try:
                    ips = float(single.get(backend, 0.0))
                    fit = CostModel._fit_mode(
                        mode, net, by_batch.get(backend, {}),
                        b0 / ips * 1e6 if ips > 0 else None, b0,
                        costs[mode])
                    if fit is not None:
                        costs[mode] = fit
                except (ValueError, TypeError, KeyError, AttributeError):
                    # one malformed backend entry must not discard the
                    # other modes' fits — THIS mode keeps its default
                    if not fallback_to_default:
                        raise
                    continue
        except (ValueError, TypeError, KeyError, AttributeError,
                OSError) as err:
            # truncated/malformed bench file (e.g. kernel_bench killed
            # mid-write) must not break serving — keep defaults
            if not fallback_to_default:
                raise
            return CostModel(dict(DEFAULT_COSTS),
                             source=f"default (unreadable bench: {err})")
        return CostModel(costs, source=str(path))


__all__ = ["MODES", "ModeCost", "CostModel", "DEFAULT_COSTS",
           "cost_hint", "flops_per_item"]

"""Granite 4.0-H (Mamba-2 + NoPE attention hybrid) against its plain float32
reference (`bench/reference/granite_hybrid.py`, token-by-token recurrence),
at a small size on the CPU with seeded random weights.

Tolerances: the program in float32 differs from the reference only in the
order of float32 operations (the chunked SSD sums decays as differences of
cumulative sums and contracts in blocks; the reference multiplies decays
token by token), so values agree to a few float32 ulps of their scale;
measured 2e-7 (logits) and 1.5e-6 (worst gradient leaf), limits 1e-5 and
1e-4 leave an order of magnitude of room.  A dropped carry between chunks,
a missed document reset or a wrong multiplier moves them by 1e-2 or more.
"""
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:   # bench/ is imported as a package from the root
    sys.path.insert(0, str(REPO))

from bench.generators import lm_train as gen  # noqa: E402
from bench.reference import granite_hybrid as ref  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.data.synthetic import DataConfig, make_batch  # noqa: E402
from repro.models import mamba2  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import ATTN_GLOBAL, MAMBA2, ShapeConfig  # noqa: E402

SMALL = dict(hidden_size=64, shared_intermediate_size=128, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, mamba_chunk_size=16, vocab_size=512)


def _small_cfg(**program) -> dict:
    cfg = json.loads((REPO / "bench/configs/granite_4_0_h_micro.json").read_text())
    cfg.update(SMALL, qat=False)
    cfg["program"] = {**cfg["program"], "dtype": "float32", "attn_chunk": 0, "ce_chunk": 0,
                      **program}
    return cfg


def _packed_batch(mcfg, seq=64, rows=2, seed=5):
    dc = DataConfig(seed=seed, packed=True, doc_len_median=12, doc_len_sigma=1.0,
                    doc_len_min=2, doc_len_max=seq)
    return make_batch(dc, mcfg, ShapeConfig("t", "train", seq, rows), 0)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# --------------------------------------------------------------------------- #
# the chunked SSD against the token-by-token recurrence
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("chunk,length,packed", [(4, 64, True), (16, 64, True),
                                                 (64, 64, True), (16, 60, True),
                                                 (16, 64, False)])
def test_chunked_ssd_matches_recurrence(chunk, length, packed):
    b, h, p, g, n = 2, 4, 8, 2, 8
    ks = jax.random.split(jax.random.key(chunk + length), 6)
    x = jax.random.normal(ks[0], (b, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, length, h)) - 1.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.0))
    B = jax.random.normal(ks[3], (b, length, g, n))
    C = jax.random.normal(ks[4], (b, length, g, n))
    seg = (jnp.cumsum(jax.random.uniform(ks[5], (b, length)) < 0.08, axis=1)
           if packed else jnp.zeros((b, length), jnp.int32))
    got = mamba2.ssd(x, dt, A, B, C, seg if packed else None, chunk)
    with jax.default_matmul_precision("highest"):
        want = ref.recurrence(x, dt, A, B, C, ref.starts(seg))
    assert _rel(got, want) < 1e-5


# --------------------------------------------------------------------------- #
# the whole model against the reference
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("packed", [True, False])
def test_logits_loss_and_every_gradient_match_the_reference(packed):
    cfg = _small_cfg()
    mcfg = gen.model_config(cfg)
    rp = ref.init(jax.random.key(3), cfg)
    pp = gen.to_program(rp, mcfg)
    batch = _packed_batch(mcfg)
    if not packed:
        batch = {k: v for k, v in batch.items() if k != "segment_ids"}
        batch["labels"] = jnp.roll(batch["tokens"], -1, axis=1)
    with jax.default_matmul_precision("highest"):
        want_logits = ref.logits(rp, batch, cfg)
    got_logits, _ = T.forward(pp, batch, mcfg)
    scale = float(jnp.max(jnp.abs(want_logits)))
    assert float(jnp.max(jnp.abs(got_logits - want_logits))) < 1e-5 * max(scale, 1.0)

    want_loss, want_grads = ref.loss_and_grads(cfg)(rp, batch)
    got_loss, got_grads = jax.value_and_grad(lambda p: T.loss_fn(p, batch, mcfg)[0])(pp)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    got = dict(gen._leaves(gen.to_reference(got_grads, mcfg)))
    want = dict(gen._leaves(want_grads))
    assert got.keys() == want.keys() and len(got) == 2 + 9 * 13 + 9
    worst = max((_rel(got[k], want[k]), k) for k in want)
    assert worst[0] < 1e-4, worst


def test_the_cells_precision_stays_near_the_reference():
    """bfloat16 matmuls with float32 accumulation, chunked attention and
    loss, layer remat: the cell's own path, within bfloat16's reach of the
    float32 reference (8 bits of mantissa over ten layers: measured 1.5e-5
    on the loss and 4.0e-2 on the worst gradient leaf, `dt_bias`)."""
    cfg = _small_cfg(dtype="bfloat16", attn_chunk=16, ce_chunk=32)
    mcfg = gen.model_config(cfg)
    rp = ref.init(jax.random.key(4), cfg)
    batch = _packed_batch(mcfg)
    want_loss, want_grads = ref.loss_and_grads(cfg)(rp, batch)
    got_loss, got_grads = jax.value_and_grad(
        lambda p: T.loss_fn(p, batch, mcfg, attn_chunk=16, ce_chunk=32)[0])(
        gen.to_program(rp, mcfg))
    assert abs(float(got_loss) - float(want_loss)) < 1e-3 * abs(float(want_loss))
    got = dict(gen._leaves(gen.to_reference(got_grads, mcfg)))
    worst = max(_rel(got[k], v) for k, v in gen._leaves(want_grads))
    assert worst < 0.1


def test_a_packed_sequence_is_its_documents_alone():
    """Attention, the SSD and the conv all reset at a document boundary:
    each document's logits in a packed row are those it gives alone."""
    cfg = _small_cfg()
    mcfg = gen.model_config(cfg)
    params = gen.to_program(ref.init(jax.random.key(6), cfg), mcfg)
    lens = [21, 5, 38]           # boundaries inside chunks of 16, one doc spans three
    toks = jax.random.randint(jax.random.key(7), (1, sum(lens)), 0, mcfg.vocab_size)
    seg = jnp.repeat(jnp.arange(len(lens)), jnp.array(lens), total_repeat_length=sum(lens))[None]
    packed, _ = T.forward(params, {"tokens": toks, "segment_ids": seg}, mcfg)
    start = 0
    for n in lens:
        alone, _ = T.forward(params, {"tokens": toks[:, start:start + n]}, mcfg)
        assert float(jnp.max(jnp.abs(packed[:, start:start + n] - alone))) < 1e-5
        start += n
    # and the boundary matters: without segment ids the later documents change
    joined, _ = T.forward(params, {"tokens": toks}, mcfg)
    assert float(jnp.max(jnp.abs(joined[:, lens[0]:] - packed[:, lens[0]:]))) > 1e-3


# --------------------------------------------------------------------------- #
# the published configuration
# --------------------------------------------------------------------------- #

def test_config_has_the_published_sizes():
    cfg = registry.get("granite-4.0-h-micro")
    types = cfg.layer_types()
    assert len(types) == 40
    assert [i for i, t in enumerate(types) if t == ATTN_GLOBAL] == [5, 15, 25, 35]
    assert all(t == MAMBA2 for i, t in enumerate(types) if i % 10 != 5)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd) == (2048, 32, 8, 64)
    assert (cfg.mamba_n_heads, cfg.mamba_head_dim, cfg.mamba_d_inner) == (64, 64, 4096)
    assert (cfg.mamba_d_state, cfg.mamba_n_groups, cfg.mamba_chunk, cfg.conv1d_width) == \
        (128, 1, 256, 4)
    assert (cfg.d_ff, cfg.vocab_size, cfg.norm_eps) == (8192, 100_352, 1e-5)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logits_scaling, cfg.attn_scale) == \
        (12.0, 0.22, 8.0, 0.015625)
    assert cfg.tie_embeddings and not cfg.rope


def test_total_params_is_the_published_count():
    cfg = registry.get("granite-4.0-h-micro")
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.key(0))
    counted = sum(x.size for x in jax.tree.leaves(shapes))
    assert counted == cfg.total_params() == cfg.params_per_token() == 3_191_396_096
    # the benchmark's cut: one period and a quarter of the vocabulary
    cell = gen.model_config(json.loads(
        (REPO / "bench/configs/granite_4_0_h_micro.json").read_text()))
    assert cell.total_params() == 797_850_560
    assert cell.total_params() == dataclasses.replace(
        cfg, n_layers=10, vocab_size=25_088).total_params()

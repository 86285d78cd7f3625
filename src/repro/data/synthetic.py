"""Deterministic synthetic data pipeline.

Every batch is a pure function of (seed, step, shard) — the property the
fault-tolerance story leans on: a restarted worker resumes at the
checkpointed step and regenerates exactly the batches it would have seen
(runtime/ft.py DataSkipAhead), and elastic re-sharding just re-slices the
same global batch.

The token stream is a mixture of Zipf-distributed unigrams and deterministic
n-gram structure, so LM losses actually *decrease* during smoke training
(pure uniform noise would pin the loss at log V).

Packed documents (`DataConfig.packed`): each row is a stream of documents
whose lengths are log-normal (median `doc_len_median`, `doc_len_sigma`,
clipped to [`doc_len_min`, `doc_len_max`]), packed greedily in order and the
last one cut at the row's end.  The batch then carries `segment_ids` (the
document index of each token) and `labels` are -100 where the next token
starts a new document, and at the row's end.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, ShapeConfig

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DataConfig:
    seed: int = 0
    zipf_a: float = 1.2
    structure_period: int = 8   # deterministic n-gram backbone
    packed: bool = False        # packed documents with segment_ids
    doc_len_median: float = 1024.0
    doc_len_sigma: float = 1.2
    doc_len_min: int = 32
    doc_len_max: int = 8192


def segment_ids(cfg: DataConfig, key: Array, batch: int, seq_len: int) -> Array:
    """(batch, seq_len) int32 document index of each position: documents of
    log-normal length packed in order, the last one cut at the row's end."""
    n = -(-seq_len // cfg.doc_len_min)          # enough documents to fill a row
    z = jax.random.normal(key, (batch, n))
    lens = jnp.clip(jnp.round(cfg.doc_len_median * jnp.exp(cfg.doc_len_sigma * z)),
                    cfg.doc_len_min, cfg.doc_len_max).astype(jnp.int32)
    ends = jnp.cumsum(lens, axis=1)
    pos = jnp.arange(seq_len, dtype=jnp.int32)
    return jax.vmap(lambda e: jnp.searchsorted(e, pos, side="right"))(ends).astype(jnp.int32)


def _batch_key(cfg: DataConfig, step: int) -> Array:
    return jax.random.fold_in(jax.random.key(cfg.seed), step)


def make_batch(cfg: DataConfig, model_cfg: ModelConfig, shape: ShapeConfig,
               step: int) -> dict[str, Array]:
    """Full global batch for `step` (host-sliced by the runner)."""
    b, s = shape.global_batch, shape.seq_len
    key = _batch_key(cfg, step)
    k_tok, k_fe, k_lab = jax.random.split(key, 3)
    v = model_cfg.vocab_size

    # Zipf-ish tokens: u^(alpha) maps uniform to a heavy head
    u = jax.random.uniform(k_tok, (b, s + 1))
    toks = (v * u ** cfg.zipf_a).astype(jnp.int32) % v
    # deterministic structure: every `period`-th token repeats the previous
    pos = jnp.arange(s + 1)
    struct = jnp.where(pos % cfg.structure_period == 0, 1, 0)
    toks = jnp.where(struct[None, :], jnp.roll(toks, 1, axis=1), toks)

    batch: dict[str, Array] = {}
    if shape.kind == "decode":
        return {"tokens": toks[:, :1]}
    if cfg.packed and model_cfg.frontend == "none":
        seg = segment_ids(cfg, jax.random.fold_in(key, 3), b, s)
        same = seg[:, 1:] == seg[:, :-1]
        labels = jnp.where(same, toks[:, 1:s], -100)
        batch = {"tokens": toks[:, :s], "segment_ids": seg}
        if shape.kind == "train":
            batch["labels"] = jnp.concatenate(
                [labels, jnp.full((b, 1), -100, labels.dtype)], axis=1)
        return batch
    if model_cfg.frontend != "audio_stub":
        batch["tokens"] = toks[:, :s]
    if model_cfg.frontend == "vision_stub":
        batch["frontend"] = jax.random.normal(
            k_fe, (b, model_cfg.frontend_len, model_cfg.frontend_dim))
    elif model_cfg.frontend == "audio_stub":
        batch["frontend"] = jax.random.normal(
            k_fe, (b, s, model_cfg.frontend_dim))
    if shape.kind == "train":
        if model_cfg.frontend == "audio_stub":
            # HuBERT-style masked-frame targets: 8% of frames predicted
            labels = jax.random.randint(k_lab, (b, s), 0, v)
            mask = jax.random.uniform(k_lab, (b, s)) < 0.08
            batch["labels"] = jnp.where(mask, labels, -100)
        else:
            labels = toks[:, 1:s + 1]
            if model_cfg.frontend == "vision_stub":
                img = jnp.arange(s)[None, :] < model_cfg.frontend_len
                labels = jnp.where(img, -100, labels)
            batch["labels"] = labels
    return batch


class DataIterator:
    """Stateful wrapper with O(1) skip-ahead (checkpoint-restore safe)."""

    def __init__(self, cfg: DataConfig, model_cfg: ModelConfig,
                 shape: ShapeConfig, start_step: int = 0):
        self.cfg, self.model_cfg, self.shape = cfg, model_cfg, shape
        self.step = start_step

    def __iter__(self) -> Iterator[dict[str, Array]]:
        return self

    def __next__(self) -> dict[str, Array]:
        b = make_batch(self.cfg, self.model_cfg, self.shape, self.step)
        self.step += 1
        return b

    def skip_to(self, step: int):
        self.step = step

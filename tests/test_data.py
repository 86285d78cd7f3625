import numpy as np

from repro.configs import registry
from repro.data.synthetic import DataConfig, DataIterator, make_batch
from repro.models.config import ShapeConfig

SHAPE = ShapeConfig("t", "train", 32, 4)


def test_deterministic_across_restart():
    cfg = registry.get_smoke("qwen2_0_5b")
    a = make_batch(DataConfig(seed=1), cfg, SHAPE, step=5)
    b = make_batch(DataConfig(seed=1), cfg, SHAPE, step=5)
    assert np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    c = make_batch(DataConfig(seed=1), cfg, SHAPE, step=6)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))


def test_labels_are_shifted_tokens():
    cfg = registry.get_smoke("qwen2_0_5b")
    b = make_batch(DataConfig(), cfg, SHAPE, 0)
    assert b["labels"].shape == b["tokens"].shape
    assert int(b["tokens"].min()) >= 0
    assert int(b["tokens"].max()) < cfg.vocab_size


def test_iterator_skip_to():
    cfg = registry.get_smoke("qwen2_0_5b")
    it = DataIterator(DataConfig(seed=2), cfg, SHAPE)
    batches = [next(it) for _ in range(4)]
    it2 = DataIterator(DataConfig(seed=2), cfg, SHAPE)
    it2.skip_to(3)
    b3 = next(it2)
    assert np.array_equal(np.asarray(b3["tokens"]),
                          np.asarray(batches[3]["tokens"]))


def test_vision_batch_masks_image_prefix():
    cfg = registry.get_smoke("phi3_vision_4_2b")
    b = make_batch(DataConfig(), cfg, SHAPE, 0)
    assert b["frontend"].shape == (4, cfg.frontend_len, cfg.frontend_dim)
    assert bool((b["labels"][:, :cfg.frontend_len] == -100).all())


def test_audio_batch_has_masked_targets():
    cfg = registry.get_smoke("hubert_xlarge")
    b = make_batch(DataConfig(), cfg, SHAPE, 0)
    assert "tokens" not in b
    frac = float((b["labels"] >= 0).mean())
    assert 0.0 < frac < 0.3


def test_packed_documents():
    cfg = registry.get_smoke("granite_4_0_h_micro")
    dc = DataConfig(seed=2 ** 31 + 3, packed=True, doc_len_median=20, doc_len_sigma=1.2,
                    doc_len_min=4, doc_len_max=64)
    shape = ShapeConfig("t", "train", 256, 4)
    b = make_batch(dc, cfg, shape, 0)
    seg, tok, lab = (np.asarray(b[k]) for k in ("segment_ids", "tokens", "labels"))
    assert seg.shape == tok.shape == lab.shape == (4, 256)
    assert (np.diff(seg, axis=1) >= 0).all() and (seg[:, 0] == 0).all()
    lengths = [np.bincount(row)[:-1] for row in seg]    # the last document is cut
    assert all(((n >= 4) & (n <= 64)).all() for n in lengths)
    same = seg[:, 1:] == seg[:, :-1]
    # a label is the next token inside a document, -100 where one starts and at the end
    assert (lab[:, :-1][same] == tok[:, 1:][same]).all()
    assert (lab[:, :-1][~same] == -100).all() and (lab[:, -1] == -100).all()
    again = make_batch(dc, cfg, shape, 0)
    assert np.array_equal(np.asarray(again["segment_ids"]), seg)
    other = make_batch(dc, cfg, shape, 1)
    assert not np.array_equal(np.asarray(other["segment_ids"]), seg)

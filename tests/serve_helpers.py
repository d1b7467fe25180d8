"""What the three serving test files share (test_serve.py,
test_serve_pool.py, test_serve_regions.py): the tiny model and the
periodic sequence it is overfit to."""

from __future__ import annotations

from mmlspark_tpu.models import build_model

PERIOD = 4


def train_lm(m, steps=30, seq=16):
    from mmlspark_tpu.testing.datagen import overfit_periodic_lm

    return overfit_periodic_lm(m, steps=steps, seq=seq, period=PERIOD)


def tiny_lm(**kw):
    cfg = dict(vocab_size=8, d_model=32, heads=2, depth=2, max_len=32)
    cfg.update(kw)
    return build_model("transformer_lm", **cfg)

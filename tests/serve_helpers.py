"""What the serving and generation test files share: the tiny model, the
periodic sequence it is overfit to, and one trained copy of each
configuration a process."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.models import build_model, generate

PERIOD = 4

TINY = dict(vocab_size=8, d_model=32, heads=2, depth=2, max_len=32)


def tiny_lm(model="transformer_lm", **kw):
    return build_model(model, **{**TINY, **kw})


def init_lm(m, seed=0):
    """Untrained variables, under ``jax.jit``: eager, ``init`` compiles a
    program an operation (7.7 s against 1.4 for the tiny model)."""
    return jax.jit(m.init)(jax.random.PRNGKey(seed),
                           jnp.zeros((1, 8), jnp.int32))


@functools.lru_cache(maxsize=None)
def _trained(model, config, steps, seq):
    from mmlspark_tpu.testing.datagen import overfit_periodic_lm

    m = tiny_lm(model, **dict(config))
    v, ids = overfit_periodic_lm(m, steps=steps, seq=seq, period=PERIOD)
    return m, v, ids


def trained_lm(model="transformer_lm", steps=30, seq=16, **kw):
    """``(graph, variables, ids)`` of the tiny model overfit to the
    periodic stream: initialised, compiled and trained once a process for
    each distinct configuration (``--dist loadfile`` keeps a file's cases
    in one process). The graph and the variables are SHARED: a test that
    edits ``graph.extra`` or donates a variable builds its own."""
    return _trained(model, tuple(sorted(kw.items())), steps, seq)


_REFERENCES: dict = {}


def ref_tokens(m, v, prompt, max_new, eos_id=None):
    """``generate()``'s tokens for one prompt, the oracle a served stream
    is held to. Under ``jax.jit``, and run as far as the model's length
    lets, then cut to ``max_new``: greedy, so a longer continuation
    begins with the shorter one. A prompt length so costs one program a
    process, whatever the budgets asked, where eager ``generate()`` costs
    a program an operation and a new scan every call.
    tests/test_generate.py holds the two to the same tokens."""
    prompt = np.asarray(prompt, np.int32)
    budget = max(max_new, (m.input_shape or (0,))[0] - len(prompt))
    key = (id(m), budget, eos_id)
    if key not in _REFERENCES:
        # the graph is kept beside its program so that its id stays its own
        _REFERENCES[key] = m, jax.jit(
            lambda v, p: generate(m, v, p, budget, eos_id=eos_id))
    out = _REFERENCES[key][1](v, prompt[None])
    return np.asarray(out)[0, :len(prompt) + max_new]

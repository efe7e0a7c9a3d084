"""Faults planted under the timed path, each of which has to make
``correct`` come out false: the CPU tests plant them at a tiny size, and
``bench/control.py --faults`` at a cell's own size on the chip. Each is
called with the engine after set-up and replaces the compiled entry
points the executor will call. (One chip: there is no exchange between
chips to leave out.)"""
from __future__ import annotations

import dataclasses


def _wrap_models(eng, prefill=None, decode=None) -> None:
    models = eng.models
    with models._lock:
        for key, lm in list(models._models.items()):
            kw = {}
            if prefill is not None:
                kw["prefill"] = prefill(lm.prefill)
            if decode is not None:
                kw["decode"] = decode(lm.decode)
            models._models[key] = dataclasses.replace(lm, **kw)


def state_unchanged(eng) -> None:
    """A decode step that returns its state (cache and token) unchanged."""
    _wrap_models(eng, decode=lambda f: (lambda p, cache, tok: (tok, cache)))


def half_batch(eng) -> None:
    """Half of a fused batch left out: its rows get the first half's."""
    def cut(tok):
        b = tok.shape[0]
        if b < 2:
            return tok
        h = b // 2
        return tok.at[h:].set(tok[: b - h])

    def pre(f):
        def g(p, toks, kw):
            tok, cache, logits = f(p, toks, kw)
            return cut(tok), cache, logits
        return g

    def dec(f):
        def g(p, cache, tok):
            t, c = f(p, cache, tok)
            return cut(t), c
        return g

    _wrap_models(eng, prefill=pre, decode=dec)


def token_altered(eng) -> None:
    """A token changed where it is produced: every decode step's output
    of a cache at an odd length is moved by one."""
    import jax.numpy as jnp

    def dec(f):
        def g(p, cache, tok):
            t, c = f(p, cache, tok)
            odd = (c["lengths"][:, None] % 2) == 1
            return jnp.where(odd, (t + 1) % 512, t), c
        return g

    _wrap_models(eng, decode=dec)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, token_altered)}

"""Engines put in the program's place: the control and planted faults.

Each wraps a ``QueryEngine`` (or anything with its entry points) and
changes what the timed path returns, so that a run shows whether the
comparison that decides ``correct`` catches it:

* ``control``: the plain reference put in the program's place, computed
  one precision below the configuration's (``BELOW``: float32 for
  float64, bfloat16 for float32);
* ``altered``: one value of each answer changed where it is produced;
* ``half``: half of the answers left out (every other request, or half
  of a draw's or a join's rows);
* ``stale``: every call returns the first call's answer, as a step that
  leaves its state (the key) unchanged.

``SpanEngine`` is no fault: it opens the ``draw`` span around a batched
draw, so that a micro-batcher's own time around it can be named.
"""
from __future__ import annotations

import numpy as np

from bench.reference import rounded

__all__ = ["Proxy", "SpanEngine", "Altered", "HalfLeftOut", "Stale",
           "Control", "planted", "BELOW"]

# The nearest precision below each that a configuration may state.
BELOW = {"float64": "float32", "float32": "bfloat16"}


class Proxy:
    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)


class SpanEngine(Proxy):
    """Opens the ``draw`` span around ``sample_batch`` and waits for it."""

    def __init__(self, engine, spans):
        super().__init__(engine)
        self._spans = spans

    def sample_batch(self, query, keys, **kw):
        import jax

        with self._spans.span("draw"):
            smp = self._engine.sample_batch(query, keys, **kw)
            jax.block_until_ready(smp.count)
        return smp


def _with(smp, **changes):
    import dataclasses

    return dataclasses.replace(smp, **changes)


class Altered(Proxy):
    def __init__(self, engine, prob_var: str):
        super().__init__(engine)
        self._p = prob_var

    def _alter(self, cols, index):
        cols = dict(cols)
        cols[self._p] = cols[self._p].at[index].add(0.5)
        return cols

    def poisson_sample(self, query, key, **kw):
        smp = self._engine.poisson_sample(query, key, **kw)
        return _with(smp, columns=self._alter(smp.columns, 0))

    def sample_batch(self, query, keys, **kw):
        smp = self._engine.sample_batch(query, keys, **kw)
        return _with(smp, columns=self._alter(smp.columns, (slice(None), 0)))

    def full_join(self, query, **kw):
        return self._alter(self._engine.full_join(query, **kw), 0)


class HalfLeftOut(Proxy):
    def __init__(self, engine):
        super().__init__(engine)
        self._lanes = 0

    def poisson_sample(self, query, key, **kw):
        smp = self._engine.poisson_sample(query, key, **kw)
        return _with(smp, count=smp.count // 2)

    def sample_batch(self, query, keys, **kw):
        import jax.numpy as jnp

        smp = self._engine.sample_batch(query, keys, **kw)
        b = int(keys.shape[0])
        lane = self._lanes + np.arange(b)
        self._lanes += b
        return _with(smp, count=jnp.where(jnp.asarray(lane % 2 == 1), 0,
                                          smp.count))

    def full_join(self, query, **kw):
        out = self._engine.full_join(query, **kw)
        n = next(iter(out.values())).shape[0]
        return {v: c[: n // 2] for v, c in out.items()}


class Stale(Proxy):
    def __init__(self, engine):
        super().__init__(engine)
        self._first = {}

    def poisson_sample(self, query, key, **kw):
        if query not in self._first:
            self._first[query] = self._engine.poisson_sample(query, key, **kw)
        return self._first[query]

    def sample_batch(self, query, keys, **kw):
        import jax
        import jax.numpy as jnp

        if query not in self._first:
            self._first[query] = self._engine.sample_batch(query, keys[:1],
                                                           **kw)
        b = int(keys.shape[0])
        return jax.tree.map(lambda x: jnp.broadcast_to(x[0], (b,) + x.shape[1:]),
                            self._first[query])


class Control(Proxy):
    """The reference sampler and join in ``precision``, in the program's
    place; ``ref_of(query)`` is the plain reference of a query shape."""

    def __init__(self, engine, ref_of, seed: int, precision: str):
        super().__init__(engine)
        self._ref_of = ref_of
        self._rng = np.random.default_rng([seed, 32])
        self._precision = precision
        self._rows = {}

    def _bag(self, query):
        if query not in self._rows:
            self._rows[query] = self._ref_of(query).bag_rows()
        return self._rows[query]

    def _one(self, query):
        return self._ref_of(query).sample(self._rng, self._precision,
                                          self._bag(query))

    def _sample(self, outs):
        import jax.numpy as jnp

        from repro.core.poisson import JoinSample

        cap = max(1, max(next(iter(o.values())).shape[0] for o in outs))
        cols = {v: jnp.asarray(np.stack([np.pad(o[v], (0, cap - o[v].shape[0]))
                                         for o in outs]))
                for v in outs[0]}
        count = jnp.asarray([next(iter(o.values())).shape[0] for o in outs])
        pos = jnp.broadcast_to(jnp.arange(cap), (len(outs), cap))
        return JoinSample(cols, pos, count, jnp.zeros(len(outs), bool))

    def poisson_sample(self, query, key, **kw):
        import jax

        return jax.tree.map(lambda x: x[0], self._sample([self._one(query)]))

    def sample_batch(self, query, keys, **kw):
        return self._sample([self._one(query)
                             for _ in range(int(keys.shape[0]))])

    def full_join(self, query, **kw):
        import jax.numpy as jnp

        ref = self._ref_of(query)
        out = ref.bag_columns(self._bag(query))
        p = ref.prob_var
        out[p] = rounded(out[p], self._precision)
        return {v: jnp.asarray(c) for v, c in out.items()}


def planted(name: str, engine, *, ref_of=None, prob_var: str = "p",
            seed: int = 0, precision: str = "float64"):
    """The engine for fault ``name`` ("sound" returns ``engine``);
    ``ref_of(query)`` gives the control its reference, which it runs one
    precision below the configuration's ``precision`` of ``prob_var``."""
    if name == "sound":
        return engine
    if name == "control":
        return Control(engine, ref_of, seed, BELOW[precision])
    if name == "altered":
        return Altered(engine, prob_var)
    if name == "half":
        return HalfLeftOut(engine)
    if name == "stale":
        return Stale(engine)
    raise ValueError(f"unknown fault {name!r}")

"""Per-learner streaming batches (paper Section 2 setting).

``LearnerStreams`` wraps a data source and yields, each round t, a pytree of
batches with leading (m, B, ...) leaves — learner i's sample E_t^i. Supports
unbalanced sampling rates B^i (Appendix C / Algorithm 2) by padding to
max(B^i) with repeated samples and exposing per-learner weights.

``next_chunk(n)`` produces the (n, m, B, ...) layout the scanned round
driver consumes. When the source implements the pure ``concept()`` /
``sample_from()`` protocol (see ``repro.data.synthetic``), the whole chunk
is drawn by ONE jitted ``lax.scan`` whose per-round key derivation is
identical to ``next()``'s — so chunked and per-round sampling yield
bitwise-equal batches while eliminating the m*n host dispatches that
dominated simulator wall-clock.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.telemetry.trace import span


class LearnerStreams:
    def __init__(self, source, m: int, batch: int = 10, seed: int = 0,
                 batch_sizes: Optional[Sequence[int]] = None, **sample_kw):
        self.source = source
        self.m = m
        self.batch = batch
        self.batch_sizes = batch_sizes
        self.sample_kw = sample_kw
        self._key = jax.random.PRNGKey(seed ^ 0x5EED)
        self._round = 0
        self._chunk_samplers: dict = {}

    @property
    def weights(self) -> Optional[jnp.ndarray]:
        if self.batch_sizes is None:
            return None
        return jnp.asarray(self.batch_sizes, jnp.float32)

    def next(self):
        """Batches for one round: leaves (m, B, ...)."""
        self._key, sub = jax.random.split(self._key)
        keys = jax.random.split(sub, self.m)
        if self.batch_sizes is None:
            batches = [self.source.sample(k, self.batch, **self.sample_kw)
                       for k in keys]
        else:
            bmax = max(self.batch_sizes)
            batches = []
            for k, bi in zip(keys, self.batch_sizes):
                b = self.source.sample(k, bi, **self.sample_kw)
                if bi < bmax:
                    reps = -(-bmax // bi)
                    b = jax.tree.map(
                        lambda x: jnp.tile(
                            x, (reps,) + (1,) * (x.ndim - 1))[:bmax], b)
                batches.append(b)
        self._round += 1
        return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

    # ------------------------------------------------------------------
    # chunked sampling (scanned-driver input layout)
    # ------------------------------------------------------------------

    @property
    def fused_chunks(self) -> bool:
        """True when whole chunks can be drawn in one compiled program."""
        return self.batch_sizes is None and hasattr(self.source, "sample_from")

    def _chunk_sampler(self, n: int):
        fn = self._chunk_samplers.get(n)
        if fn is None:
            m, batch, kw, source = self.m, self.batch, self.sample_kw, self.source

            def sample_chunk(key, concept):
                def per_round(key, _):
                    key, sub = jax.random.split(key)      # == next()'s splits
                    keys = jax.random.split(sub, m)
                    b = jax.vmap(
                        lambda k: source.sample_from(concept, k, batch, **kw)
                    )(keys)
                    return key, b

                return jax.lax.scan(per_round, key, None, length=n)

            fn = self._chunk_samplers[n] = jax.jit(sample_chunk)
        return fn

    def next_chunk(self, n: int, on_round=None):
        """Batches for n consecutive rounds: leaves (n, m, B, ...), the
        input layout of ``DecentralizedLearner.run_chunk``. ``on_round(i)``
        (i = 0..n-1) runs before round i's samples are drawn — the hook for
        host-side per-round events such as concept drift; passing it forces
        the per-round host path (the concept may change mid-chunk)."""
        if n < 1:
            raise ValueError(f"chunk length must be >= 1, got {n}")
        with span("repro.streams.next_chunk", round=self._round):
            if on_round is None and self.fused_chunks:
                self._key, batches = self._chunk_sampler(n)(
                    self._key, self.source.concept())
                self._round += n
                return batches
            rounds = []
            for i in range(n):
                if on_round is not None:
                    on_round(i)
                rounds.append(self.next())
            return jax.tree.map(lambda *xs: jnp.stack(xs), *rounds)

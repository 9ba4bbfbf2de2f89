"""Plain JAX reference of the dynamic-averaging fleet round.

What the benchmark's cells run through ``run_protocol_training``, written
again from the paper and from nothing of the program: m learners, each
taking one mini-batch SGD step per round on its own stream (Kamp et al.
2018, Section 2), and every ``b`` rounds the dynamic averaging protocol
(Algorithm 1): learners whose squared distance to the reference model
exceeds Delta violate; the coordinator grows the violators' cohort by the
learners farthest from the reference until the cohort's mean lies within
Delta of it (or every learner is in); the cohort adopts its mean; a sync
of the whole fleet moves the reference; a violation counter that reaches
m forces a full sync.

The member model comes from the cell's member module
(``perfbench/members/``): its ``reference_init`` and ``reference_loss``.
Weights and data come from the seed exactly as the program's entry point
derives them (``PRNGKey(seed)`` split in three, the first for the
member's initialisation; ``PRNGKey(seed ^ 0x5EED)`` split once per round
and once per learner for the streams), so the reference and the program
start from the same fleet and see the same batches.

The fleet is held as the stacked leaves, each leaf ``(m, *leaf.shape)``,
as the program holds it; no array joins the leaves. A learner's squared
distance is a float32 sum over leaves; cohort means, commits, the spread
and the change norms are taken leaf by leaf. Learners are updated, and
their distances taken, ``member.LEARNER_BLOCK`` at a time. Peak device
memory is the fleet, plus at most three model-sized arrays at once (the
reference ``r``, the start ``w0``, and the cohort's running sum or its
mean), plus one learner block's gradients and activations. (The planted
fault ``groups`` > 1 may hold one more fleet-sized temporary.)

``dtype`` float32 runs at full matmul precision (the reference);
bfloat16 is the control, the precision below the configuration's
float32: it casts the fleet's leaves and the batch's floating leaves,
and leaves integer leaves (labels, tokens) as they are.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

STREAM_SALT = 0x5EED


# ---------------------------------------------------------------------------
# streams
# ---------------------------------------------------------------------------

def round_batches(source, m: int, batch: int, seed: int, rounds: int):
    """The per-round (m, B, ...) batches of the fleet's streams: one key
    split per round, then one per learner."""
    concept = source.concept()
    draw = jax.jit(jax.vmap(
        lambda k: source.sample_from(concept, k, batch)))
    key = jax.random.PRNGKey(seed ^ STREAM_SALT)
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        yield draw(jax.random.split(sub, m))


def _cast_batch(bt, dtype):
    """The batch's floating leaves in ``dtype``; integer leaves as they
    are."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, bt)


# ---------------------------------------------------------------------------
# per-leaf fleet arithmetic
# ---------------------------------------------------------------------------

def _blocks(x, block: int):
    """(m, ...) -> (m // block, block, ...): a view, no data moves."""
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def _sq_dists(fleet, model, block: int):
    """(m,) float32: each learner's squared distance to ``model``, summed
    over leaves, one block of learners at a time so that no fleet-sized
    temporary is made."""
    total = 0.0
    for x, r in zip(jax.tree.leaves(fleet), jax.tree.leaves(model)):
        r32 = r.astype(jnp.float32)

        def one(xb, r32=r32):
            d = xb.astype(jnp.float32) - r32[None]
            return jnp.sum(d * d, axis=tuple(range(1, xb.ndim)))

        total = total + jax.lax.map(one, _blocks(x, block)).reshape(-1)
    return total


def _cohort_mean(fleet, mask, groups: int):
    """Per leaf, the mean of the cohort's learners. ``groups`` > 1 is a
    planted fault: the fleet is cut into that many contiguous groups (the
    shards of a sharded fleet) and each group's cohort mean is taken on
    its own, as if the exchange between chips were left out; each leaf's
    mean is then ``(groups, *leaf.shape)``."""
    def one(x):
        w = mask.astype(x.dtype)
        if groups == 1:
            return jnp.tensordot(w, x, axes=1) / jnp.maximum(jnp.sum(w), 1)
        g = w.reshape(groups, -1)
        means = jnp.einsum("gk,gk...->g...", g, _blocks(x, g.shape[1]))
        n = jnp.maximum(jnp.sum(g, axis=1), 1)
        return means / n.reshape((groups,) + (1,) * (x.ndim - 1))
    return jax.tree.map(one, fleet)


def _where_rows(mask, new, old):
    return jnp.where(mask.reshape((-1,) + (1,) * (old.ndim - 1)), new, old)


def sync_round(fleet, r, v, *, delta: float, block: int, groups: int = 1):
    """One check of Algorithm 1 with the max-distance augmentation.
    Returns (fleet, r, v, synced, full, cohort size)."""
    m = jax.tree.leaves(fleet)[0].shape[0]
    dists = _sq_dists(fleet, r, block)
    violated = dists > delta
    nhot = jnp.sum(violated).astype(jnp.int32)

    def safe(s, ws):
        ws = jnp.where(ws > 0, ws, 1)
        return sum(jnp.sum(jnp.square(a.astype(jnp.float32) / ws
                                      - b.astype(jnp.float32)))
                   for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(r)))

    def fire(fleet, r, v):
        v_new = v + nhot
        force = v_new >= m
        mask0 = jnp.where(force, jnp.ones_like(violated), violated)
        v_reset = jnp.where(force, 0, v_new)
        s0 = jax.tree.map(
            lambda x: jnp.tensordot(mask0.astype(x.dtype), x, axes=1),
            fleet)
        ws0 = jnp.sum(mask0).astype(jnp.float32)

        def cond(c):
            mask, _, _, d = c
            return jnp.any(~mask) & (d > delta)

        def body(c):
            mask, s, ws, _ = c
            nxt = jnp.argmax(jnp.where(mask, -jnp.inf, dists))
            mask = mask.at[nxt].set(True)
            s = jax.tree.map(lambda a, x: a + x[nxt], s, fleet)
            ws = ws + 1
            return mask, s, ws, safe(s, ws)

        mask, _, _, _ = jax.lax.while_loop(
            cond, body, (mask0, s0, ws0, safe(s0, ws0)))
        full = jnp.all(mask)
        mean = _cohort_mean(fleet, mask, groups)
        if groups == 1:
            fleet = jax.tree.map(lambda x, mu: _where_rows(mask, mu[None], x),
                                 fleet, mean)
            r = jax.tree.map(lambda mu, ri: jnp.where(full, mu, ri), mean, r)
        else:
            # each learner adopts its own group's mean
            def commit(x, mu):
                k = mask.reshape((groups, m // groups) + (1,) * (x.ndim - 1))
                return jnp.where(k, mu[:, None], _blocks(x, m // groups)
                                 ).reshape(x.shape)

            fleet = jax.tree.map(commit, fleet, mean)
            r = jax.tree.map(
                lambda mu, ri: jnp.where(full, jnp.mean(mu, axis=0), ri),
                mean, r)
        v = jnp.where(full, 0, v_reset)
        return fleet, r, v, jnp.int32(1), full, jnp.sum(mask).astype(
            jnp.int32)

    def skip(fleet, r, v):
        return fleet, r, v, jnp.int32(0), jnp.bool_(False), jnp.int32(0)

    return jax.lax.cond(nhot > 0, fire, skip, fleet, r, v)


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------

class Readings(NamedTuple):
    leaf_names: tuple
    leaf_sizes: np.ndarray      # (leaves,) parameters per learner
    round_loss: np.ndarray      # (rounds,) fleet loss of each round
    change_norm: np.ndarray     # (leaves,) ||theta_R - theta_0|| per leaf
    first_grad_norm: np.ndarray  # (leaves,) ||grad|| of round 1 per leaf
    spread: float               # sum_i ||theta_i - mean theta||^2 at the end
    ledger: dict                # syncs, full_syncs, model_up (cohort sizes)


def _block_size(m: int, want: int) -> int:
    b = min(m, want)
    while m % b:
        b -= 1
    return b


def run_fleet(member, model_cfg: dict, traffic: dict, source, seed: int,
              rounds: int, *, dtype=jnp.float32, fault: Optional[str] = None,
              groups: int = 1,
              learner_block: Optional[int] = None) -> Readings:
    """Drive the reference fleet of ``member`` (a module of
    ``perfbench/members/``) for ``rounds`` rounds from ``seed``.

    ``fault`` plants one of the faults the benchmark must catch, in the
    reference put in the program's place: ``"frozen"`` (a step that
    returns its state unchanged), ``"half_batch"`` (half of each batch
    left out, the mean taken over the rest); ``groups`` > 1 leaves out
    the exchange between that many shards."""
    m, batch = int(traffic["m"]), int(traffic["batch"])
    lr, b, delta = (float(traffic["learning_rate"]), int(traffic["b"]),
                    float(traffic["delta"]))
    if traffic["protocol"] != "dynamic" or traffic["optimizer"] != "sgd":
        raise ValueError("the reference runs the dynamic protocol with SGD")
    if traffic["augmentation"] != "max_distance":
        raise ValueError("the reference runs the max_distance augmentation")
    precision = "highest" if dtype == jnp.float32 else "default"

    k_init, _, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    model = member.reference_init(model_cfg, k_init)    # float32, as made
    flat, _ = jax.tree_util.tree_flatten_with_path(model)
    names = tuple(jax.tree_util.keystr(path) for path, _ in flat)
    sizes = np.asarray([int(np.prod(x.shape)) for _, x in flat])
    w0 = jax.tree.map(lambda x: x.astype(dtype), model)
    del model
    blk = _block_size(m, learner_block or member.LEARNER_BLOCK)
    half = fault == "half_batch"

    def one_loss(params, bt):
        return member.reference_loss(model_cfg, params, bt, half_batch=half)

    @functools.partial(jax.jit, donate_argnums=0)
    def update(fleet, start, bt):
        def rows(x):
            return jax.lax.dynamic_slice_in_dim(x, start, blk, 0)

        params = jax.tree.map(rows, fleet)
        losses, grads = jax.vmap(jax.value_and_grad(one_loss))(
            params, jax.tree.map(rows, bt))
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        if fault == "frozen":
            new = params
        else:
            new = jax.tree.map(
                lambda p, g: p - jnp.asarray(lr, p.dtype) * g, params, grads)
        fleet = jax.tree.map(
            lambda x, n: jax.lax.dynamic_update_slice_in_dim(x, n, start, 0),
            fleet, new)
        g_sq = jnp.stack([jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree.leaves(grads)])
        return fleet, losses.astype(jnp.float32), g_sq

    sync = jax.jit(functools.partial(
        sync_round, delta=delta, block=blk, groups=groups),
        donate_argnums=0)

    @jax.jit
    def change_sq(fleet, w0):
        """(leaves, blocks): per leaf and learner block, sum ||theta_i -
        theta_0||^2."""
        def leaf(x, w):
            w32 = w.astype(jnp.float32)
            return jax.lax.map(
                lambda xb: jnp.sum(jnp.square(xb.astype(jnp.float32)
                                              - w32[None])),
                _blocks(x, blk))
        return jnp.stack([leaf(x, w) for x, w in zip(jax.tree.leaves(fleet),
                                                     jax.tree.leaves(w0))])

    @jax.jit
    def spread(fleet):
        mean = jax.tree.map(lambda x: jnp.mean(x.astype(jnp.float32), axis=0),
                            fleet)
        return jnp.sum(_sq_dists(fleet, mean, blk))

    round_loss, grad_sq = [], None
    ledger = {"syncs": 0, "full_syncs": 0, "model_up": 0}
    with jax.default_matmul_precision(precision):
        fleet = jax.tree.map(
            lambda w: jnp.broadcast_to(w[None], (m,) + w.shape).copy(), w0)
        r, v = w0, jnp.int32(0)
        batches = round_batches(source, m, batch, seed, rounds)
        for t, bt in enumerate(batches, start=1):
            if dtype != jnp.float32:
                bt = _cast_batch(bt, dtype)
            total, gsq = 0.0, 0.0
            for start in range(0, m, blk):
                fleet, losses, g2 = update(fleet, start, bt)
                total += float(np.sum(np.asarray(losses, np.float64)))
                gsq = gsq + np.asarray(g2, np.float64)
            round_loss.append(total)
            if grad_sq is None:
                grad_sq = gsq
            if t % b == 0:
                fleet, r, v, fired, full, size = sync(fleet, r, v)
                ledger["syncs"] += int(fired)
                ledger["full_syncs"] += int(full)
                ledger["model_up"] += int(size)
        change = np.sum(np.asarray(change_sq(fleet, w0), np.float64), axis=1)
        fleet_spread = float(spread(fleet))
    return Readings(names, sizes, np.asarray(round_loss), np.sqrt(change),
                    np.sqrt(grad_sq), fleet_spread, ledger)

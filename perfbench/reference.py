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

Weights and data come from the seed exactly as the program's entry point
derives them (``PRNGKey(seed)`` split in three, the first for a Glorot
initialisation; ``PRNGKey(seed ^ 0x5EED)`` split once per round and once
per learner for the streams), so the reference and the program start from
the same fleet and see the same batches.

The fleet is held as one (m, P) plane and updated in blocks of learners,
so that a fleet of 1,024 MNIST learners fits one chip. ``dtype`` float32
runs at full matmul precision (the reference); bfloat16 is the control,
the precision below the configuration's float32.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

STREAM_SALT = 0x5EED


# ---------------------------------------------------------------------------
# the member model
# ---------------------------------------------------------------------------

def init_model(spec, input_shape, key, dtype=jnp.float32):
    """Glorot-uniform weights and zero biases, one key split per layer
    with parameters. Returns ``{"layers": [per-layer dict]}``."""
    if len(input_shape) == 1:
        h = w = c = 0
        flat = input_shape[0]
    else:
        h, w, c = input_shape
        flat = None
    layers = []
    for layer in spec:
        kind = layer[0]
        if kind == "conv":
            _, c_out, k, s = layer
            key, sub = jax.random.split(key)
            lim = math.sqrt(6.0 / (k * k * (c + c_out)))
            layers.append({
                "w": jax.random.uniform(sub, (k, k, c, c_out), dtype,
                                        -lim, lim),
                "b": jnp.zeros((c_out,), dtype)})
            h, w, c = (h - k) // s + 1, (w - k) // s + 1, c_out
        elif kind == "pool":
            layers.append({})
            h, w = h // layer[1], w // layer[1]
        elif kind == "flatten":
            layers.append({})
            if c:
                flat = h * w * c
        elif kind == "dense":
            key, sub = jax.random.split(key)
            lim = math.sqrt(6.0 / (flat + layer[1]))
            layers.append({
                "w": jax.random.uniform(sub, (flat, layer[1]), dtype,
                                        -lim, lim),
                "b": jnp.zeros((layer[1],), dtype)})
            flat = layer[1]
        elif kind == "dropout":
            layers.append({})
        else:
            raise ValueError(f"unknown layer {layer!r}")
    return {"layers": layers}


def forward(spec, params, x):
    """(B, H, W, C) or (B, d) -> (B, outputs). Dropout is the identity:
    the fleet's loss is evaluated without a dropout key."""
    n_dense = sum(1 for layer in spec if layer[0] == "dense")
    seen = 0
    for layer, p in zip(spec, params["layers"]):
        kind = layer[0]
        if kind == "conv":
            s = layer[3]
            x = jax.lax.conv_general_dilated(
                x, p["w"], window_strides=(s, s), padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = jax.nn.relu(x + p["b"])
        elif kind == "pool":
            k = layer[1]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, k, k, 1), (1, k, k, 1), "VALID")
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif kind == "dense":
            seen += 1
            x = x @ p["w"] + p["b"]
            if seen < n_dense:
                x = jax.nn.relu(x)
    return x


def loss(spec, num_outputs, params, batch, half_batch=False):
    """Squared error for a single output, else softmax cross-entropy
    (log-softmax in float32). ``half_batch`` is a planted fault: the mean
    over the first half of the batch only."""
    x, y = batch["x"], batch["y"]
    if half_batch:
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    out = forward(spec, params, x)
    if num_outputs == 1:
        return jnp.mean(jnp.square(out[:, 0].astype(jnp.float32)
                                   - y.astype(jnp.float32)))
    lp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=-1))


# ---------------------------------------------------------------------------
# the fleet plane
# ---------------------------------------------------------------------------

class Layout(NamedTuple):
    names: tuple      # leaf names, as jax.tree_util.keystr gives them
    shapes: tuple
    offsets: tuple    # start column of each leaf in a plane row
    size: int         # P
    treedef: object


def layout_of(model) -> Layout:
    flat, treedef = jax.tree_util.tree_flatten_with_path(model)
    names = tuple(jax.tree_util.keystr(path) for path, _ in flat)
    shapes = tuple(tuple(x.shape) for _, x in flat)
    sizes = [int(np.prod(s)) for s in shapes]
    offsets = tuple(int(o) for o in np.cumsum([0] + sizes[:-1]))
    return Layout(names, shapes, offsets, int(sum(sizes)), treedef)


def ravel_rows(lay: Layout, tree):
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    return jnp.concatenate([x.reshape(n, -1) for x in leaves], axis=1)


def unravel_rows(lay: Layout, rows):
    n = rows.shape[0]
    leaves = [rows[:, o:o + int(np.prod(s))].reshape((n,) + s)
              for o, s in zip(lay.offsets, lay.shapes)]
    return jax.tree_util.tree_unflatten(lay.treedef, leaves)


def leaf_sq_norms(lay: Layout, rows):
    """Per-leaf sum of squares over a block of plane rows -> (leaves,)."""
    sq = rows.astype(jnp.float32) ** 2
    return jnp.stack([jnp.sum(sq[:, o:o + int(np.prod(s))])
                      for o, s in zip(lay.offsets, lay.shapes)])


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


# ---------------------------------------------------------------------------
# Algorithm 1
# ---------------------------------------------------------------------------

def _row_dists(X, r, block: int):
    """Squared distance of each plane row to r, one block of rows at a
    time so that no plane-sized temporary is made."""
    m, p = X.shape
    blocks = X.reshape(m // block, block, p)

    def one(xb):
        d = xb.astype(jnp.float32) - r.astype(jnp.float32)[None]
        return jnp.sum(d * d, axis=1)

    return jax.lax.map(one, blocks).reshape(m)


def _cohort_mean(X, mask, groups: int):
    """Mean of the cohort's rows. ``groups`` > 1 is a planted fault: the
    fleet is cut into that many contiguous groups (the shards of a
    sharded fleet) and each member takes its own group's cohort mean,
    as if the exchange between chips were left out."""
    w = mask.astype(X.dtype)
    if groups == 1:
        return (w @ X) / jnp.maximum(jnp.sum(w), 1)
    m = X.shape[0]
    g = w.reshape(groups, m // groups)
    Xg = X.reshape(groups, m // groups, X.shape[1])
    means = jnp.einsum("gk,gkp->gp", g, Xg) / jnp.maximum(
        jnp.sum(g, axis=1, keepdims=True), 1)
    return jnp.repeat(means, m // groups, axis=0)


def sync_round(X, r, v, *, delta: float, block: int, groups: int = 1):
    """One check of Algorithm 1 with the max-distance augmentation.
    Returns (X, r, v, synced, full, cohort size)."""
    m = X.shape[0]
    dists = _row_dists(X, r, block)
    violated = dists > delta
    nhot = jnp.sum(violated).astype(jnp.int32)

    def safe(s, ws):
        d = s / jnp.where(ws > 0, ws, 1) - r
        return jnp.sum(d * d)

    def fire(X, r, v):
        v_new = v + nhot
        force = v_new >= m
        mask0 = jnp.where(force, jnp.ones_like(violated), violated)
        v_reset = jnp.where(force, 0, v_new)
        w0 = mask0.astype(X.dtype)
        s0 = w0 @ X
        ws0 = jnp.sum(w0)

        def cond(c):
            mask, _, _, d = c
            return jnp.any(~mask) & (d > delta)

        def body(c):
            mask, s, ws, _ = c
            nxt = jnp.argmax(jnp.where(mask, -jnp.inf, dists))
            mask = mask.at[nxt].set(True)
            s = s + X[nxt]
            ws = ws + 1
            return mask, s, ws, safe(s, ws)

        mask, _, _, _ = jax.lax.while_loop(
            cond, body, (mask0, s0, ws0, safe(s0, ws0)))
        full = jnp.all(mask)
        mean = _cohort_mean(X, mask, groups)
        if groups == 1:
            X = jnp.where(mask[:, None], mean[None], X)
            r = jnp.where(full, mean, r)
        else:
            X = jnp.where(mask[:, None], mean, X)
            r = jnp.where(full, jnp.mean(mean, axis=0), r)
        v = jnp.where(full, 0, v_reset)
        return X, r, v, jnp.int32(1), full, jnp.sum(mask).astype(jnp.int32)

    def skip(X, r, v):
        return X, r, v, jnp.int32(0), jnp.bool_(False), jnp.int32(0)

    return jax.lax.cond(nhot > 0, fire, skip, X, r, v)


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


def run_fleet(model_cfg: dict, traffic: dict, source, seed: int,
              rounds: int, *, dtype=jnp.float32, fault: Optional[str] = None,
              groups: int = 1, learner_block: int = 64) -> Readings:
    """Drive the reference fleet for ``rounds`` rounds from ``seed``.

    ``fault`` plants one of the faults the benchmark must catch, in the
    reference put in the program's place: ``"frozen"`` (a step that
    returns its state unchanged), ``"half_batch"`` (half of each batch
    left out, the mean taken over the rest); ``groups`` > 1 leaves out
    the exchange between that many shards."""
    spec = tuple(tuple(layer) for layer in model_cfg["cnn_spec"])
    input_shape = tuple(model_cfg["input_shape"])
    num_outputs = int(model_cfg["num_outputs"])
    m, batch = int(traffic["m"]), int(traffic["batch"])
    lr, b, delta = (float(traffic["learning_rate"]), int(traffic["b"]),
                    float(traffic["delta"]))
    if traffic["protocol"] != "dynamic" or traffic["optimizer"] != "sgd":
        raise ValueError("the reference runs the dynamic protocol with SGD")
    if traffic["augmentation"] != "max_distance":
        raise ValueError("the reference runs the max_distance augmentation")
    precision = "highest" if dtype == jnp.float32 else "default"

    k_init, _, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    model = init_model(spec, input_shape, k_init)       # float32, as made
    lay = layout_of(model)
    w0 = ravel_rows(lay, jax.tree.map(lambda x: x[None], model))[0]
    w0 = w0.astype(dtype)
    blk = _block_size(m, learner_block)
    half = fault == "half_batch"

    @functools.partial(jax.jit, donate_argnums=0)
    def update(X, start, xb, yb):
        rows = jax.lax.dynamic_slice_in_dim(X, start, blk, 0)
        params = unravel_rows(lay, rows)
        losses, grads = jax.vmap(jax.value_and_grad(
            lambda p, bt: loss(spec, num_outputs, p, bt, half)))(
            params, {"x": xb, "y": yb})
        g = ravel_rows(lay, grads).astype(dtype)
        new = rows if fault == "frozen" else rows - jnp.asarray(lr, dtype) * g
        X = jax.lax.dynamic_update_slice_in_dim(X, new, start, 0)
        return X, losses.astype(jnp.float32), leaf_sq_norms(lay, g)

    sync = jax.jit(functools.partial(
        sync_round, delta=delta, block=_block_size(m, 64), groups=groups),
        donate_argnums=0)

    @jax.jit
    def change_sq(X, start):
        rows = jax.lax.dynamic_slice_in_dim(X, start, blk, 0)
        return leaf_sq_norms(lay, rows.astype(jnp.float32)
                             - w0.astype(jnp.float32)[None])

    @jax.jit
    def spread(X):
        mean = jnp.mean(X.astype(jnp.float32), axis=0)
        return jnp.sum(_row_dists(X, mean, blk))

    round_loss, grad_sq = [], None
    ledger = {"syncs": 0, "full_syncs": 0, "model_up": 0}
    with jax.default_matmul_precision(precision):
        X = jnp.broadcast_to(w0[None], (m, lay.size)).copy()
        r, v = w0, jnp.int32(0)
        batches = round_batches(source, m, batch, seed, rounds)
        for t, bt in enumerate(batches, start=1):
            x = bt["x"].astype(dtype) if dtype != jnp.float32 else bt["x"]
            y = bt["y"]
            total, gsq = 0.0, 0.0
            for start in range(0, m, blk):
                X, losses, g2 = update(X, start, x[start:start + blk],
                                       y[start:start + blk])
                total += float(np.sum(np.asarray(losses, np.float64)))
                gsq = gsq + np.asarray(g2, np.float64)
            round_loss.append(total)
            if grad_sq is None:
                grad_sq = gsq
            if t % b == 0:
                X, r, v, fired, full, size = sync(X, r, v)
                ledger["syncs"] += int(fired)
                ledger["full_syncs"] += int(full)
                ledger["model_up"] += int(size)
        change = sum(np.asarray(change_sq(X, s), np.float64)
                     for s in range(0, m, blk))
        fleet_spread = float(spread(X))
    sizes = np.asarray([int(np.prod(sh)) for sh in lay.shapes])
    return Readings(lay.names, sizes, np.asarray(round_loss),
                    np.sqrt(change),
                    np.sqrt(grad_sq), fleet_spread, ledger)

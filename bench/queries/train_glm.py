"""``scan(table).train_glm(features, label, grid, epochs)``: K logistic
regressions trained by minibatch SGD in one pass over the data per
epoch, one per ``(lr, l2)`` pair of the grid; the answer is the K weight
vectors and their final losses.

Traffic entry keys: ``table``, ``features`` (a column group of the
configuration), ``label``, ``epochs``, ``minibatch`` (the engine's, 16),
``grids``: a list of grids, each a list of ``[lr, l2]`` pairs, one per
model.

The reference is ``chip_smoke.ref_glm`` written with ``lax.scan`` on the
device: float32 at ``highest`` matmul precision, the engine's update
order (minibatches in table order, the K models side by side), and the
mean logistic loss plus ``l2 * |x|^2``.  The control is the same
computation in bfloat16.
"""
from __future__ import annotations

import functools

import numpy as np


def tables(spec: dict) -> list:
    return [spec["table"]]


def features(spec: dict, config: dict) -> list:
    from bench.data import column_names
    group = spec["features"]
    cols = config["tables"][spec["table"]]["columns"]
    n = int(cols[group].get("count", 1))
    names = column_names({"columns": {group: cols[group]}})
    assert len(names) == n
    return names


def draw(spec: dict, config: dict, sizes: dict, rng, n: int) -> list:
    """The traffic's grids, taken in turn from an order drawn from the
    seed.  Every seed runs the same grids: the engine compiles a grid's
    values into its program, so a grid of its own per seed would compile
    in every run's set-up."""
    feats = features(spec, config)
    grids = [[(float(lr), float(l2)) for lr, l2 in g] for g in spec["grids"]]
    order = rng.permutation(len(grids))
    return [{"grid": grids[order[i % len(grids)]], "features": feats}
            for i in range(n)]


def build(spec: dict, p: dict):
    from repro.core.sgd_glm import HyperParams
    from repro.query import Q
    grid = [HyperParams(lr, l2) for lr, l2 in p["grid"]]
    return Q.scan(spec["table"]).train_glm(
        p["features"], spec["label"], grid, epochs=int(spec["epochs"]))


def fetch(value):
    xs, losses = value
    return np.asarray(xs), np.asarray(losses)


def work_rows(spec: dict, sizes: dict) -> int:
    """Rows times epochs: the K models share each pass."""
    return sizes[spec["table"]] * int(spec["epochs"])


def least_bytes(spec: dict, config: dict, sizes: dict) -> int:
    """Features and label, once per epoch for all K models, and once more
    for the losses of the trained models."""
    n_features = len(features(spec, config))
    return 4 * (n_features + 1) * sizes[spec["table"]] \
        * (int(spec["epochs"]) + 1)


def flops(spec: dict, config: dict, sizes: dict) -> int:
    """Operations of the K models' products, a multiply-add counting two:
    per row and epoch, the forward product and the gradient (2 x 2 x
    features each), and once more the forward product for the losses."""
    n_features = len(features(spec, config))
    return sizes[spec["table"]] * len(spec["grids"][0]) * n_features \
        * (4 * int(spec["epochs"]) + 2)


@functools.lru_cache(maxsize=None)
def _trainer(dtype_name: str, mb: int, epochs: int):
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def run(a_cols, b, lr, l2):
        # features on the leading axis: rows fill the chip's 128 lanes,
        # where a (rows, 28) layout would pad 28 to 128
        a = jnp.stack(a_cols).astype(dtype)
        d, m = a.shape
        b = b.astype(dtype)
        lr = lr.astype(dtype)[:, None]
        l2 = l2.astype(dtype)[:, None]
        inv = jnp.asarray(1.0 / mb, dtype)
        two = jnp.asarray(2.0, dtype)

        def step(i, x):
            ai = jax.lax.dynamic_slice(a, (0, i * mb), (d, mb))
            bi = jax.lax.dynamic_slice(b, (i * mb,), (mb,))
            z = x @ ai
            g = (jax.nn.sigmoid(z) - bi) @ ai.T * inv
            return x - lr * (g + two * l2 * x)

        x = jnp.zeros((lr.shape[0], d), dtype)
        for _ in range(epochs):
            x = jax.lax.fori_loop(0, m // mb, step, x)
        pr = jax.nn.sigmoid(x @ a)
        eps = jnp.asarray(1e-7, dtype)
        j = -(b * jnp.log(pr + eps) + (1 - b) * jnp.log(1 - pr + eps))
        loss = jnp.mean(j.astype(jnp.float32), axis=1) \
            + (l2[:, 0] * jnp.sum(jnp.square(x), axis=1)).astype(jnp.float32)
        return x.astype(jnp.float32), loss

    return run


def _train(spec: dict, data, p: dict, dtype_name: str):
    import jax
    t = data.device[spec["table"]]
    run = _trainer(dtype_name, int(spec["minibatch"]), int(spec["epochs"]))
    lr = np.asarray([g[0] for g in p["grid"]], np.float32)
    l2 = np.asarray([g[1] for g in p["grid"]], np.float32)
    with jax.default_matmul_precision("highest"):
        xs, losses = run([t[c] for c in p["features"]], t[spec["label"]],
                         lr, l2)
    return np.asarray(xs), np.asarray(losses)


class Reference:
    def __init__(self, spec: dict, data):
        self.spec, self.data = spec, data

    def answer(self, p: dict):
        return _train(self.spec, self.data, p, "float32")


def control(spec: dict, data, params: list) -> list:
    return [_train(spec, data, p, "bfloat16") for p in params]


def compare(got: list, want: list) -> dict:
    """Worst model of any answer: the distance of its weight vector from
    the reference's over the reference's norm, and its loss's relative
    distance."""
    w_err = l_err = 0.0
    for (xs, losses), (rx, rl) in zip(got, want):
        w = np.linalg.norm(xs - rx, axis=1) / np.linalg.norm(rx, axis=1)
        w_err = max(w_err, float(np.max(w)))
        l_err = max(l_err, float(np.max(np.abs(losses - rl) / rl)))
    return {"weight_rel_err": w_err, "loss_rel_err": l_err}

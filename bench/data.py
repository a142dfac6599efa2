"""Tables of a configuration, made from ``--seed``.

A configuration file (``bench/configs/<name>.json``) lists its tables,
each with a row count and its columns.  A column names a distribution:

- ``uniform``: integers drawn uniformly from ``lo`` to ``hi``, both in;
- ``normal``: float32 from the standard normal; ``count`` expands the
  entry into ``<name>0 .. <name><count-1>``;
- ``planted_linear``: a float32 0/1 label, ``x . w + noise > 0`` over the
  columns of group ``of``, with ``w`` standard normal and the noise of
  scale ``noise``.

A table is made on the device in one jitted call, so set-up pays no host
generation and no transfer.
"""
from __future__ import annotations

import zlib

import numpy as np


def seed_words(seed: int, salt: str) -> tuple:
    """Two 32-bit words from any whole ``seed`` and a salt: the same seed
    gives the same words, large seeds keep all their bits."""
    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1),
                                 zlib.crc32(salt.encode())])
    w = ss.generate_state(2)
    return int(w[0]), int(w[1])


def jax_key(seed: int, salt: str):
    import jax
    a, b = seed_words(seed, salt)
    return jax.random.fold_in(jax.random.key(a), b)


def np_rng(seed: int, salt: str) -> np.random.Generator:
    return np.random.default_rng(list(seed_words(seed, salt)))


def table_rows(spec: dict, rows_div: int = 1) -> int:
    """Rows of a table; a rehearsal divides them by ``rows_div`` (kept a
    multiple of 16, the trainer's minibatch)."""
    rows = int(spec["rows"])
    if rows_div > 1:
        rows = max(rows // rows_div // 16 * 16, 1024)
    return rows


def column_names(spec: dict) -> list:
    out = []
    for name, col in spec["columns"].items():
        if "count" in col:
            out.extend(f"{name}{i}" for i in range(int(col["count"])))
        else:
            out.append(name)
    return out


def make_table(spec: dict, seed: int, table: str,
               rows_div: int = 1) -> dict:
    """``{column: device array}`` for one table of a configuration."""
    import jax
    import jax.numpy as jnp

    rows = table_rows(spec, rows_div)
    cols = spec["columns"]

    @jax.jit
    def gen(key):
        out = {}
        keys = jax.random.split(key, len(cols))
        groups = {}
        for k, (name, c) in zip(keys, cols.items()):
            if c["dist"] == "uniform":
                out[name] = jax.random.randint(k, (rows,), int(c["lo"]),
                                               int(c["hi"]) + 1, jnp.int32)
            elif c["dist"] == "normal":
                n = int(c.get("count", 1))
                x = jax.random.normal(k, (n, rows), jnp.float32)
                groups[name] = x
                if "count" in c:
                    out.update({f"{name}{i}": x[i] for i in range(n)})
                else:
                    out[name] = x[0]
            elif c["dist"] == "planted_linear":
                x = groups[c["of"]]
                kw, kn = jax.random.split(k)
                w = jax.random.normal(kw, (x.shape[0],), jnp.float32)
                z = jnp.einsum("fr,f->r", x, w,
                               precision=jax.lax.Precision.HIGHEST)
                z = z + float(c["noise"]) * jax.random.normal(
                    kn, (rows,), jnp.float32)
                out[name] = (z > 0).astype(jnp.float32)
            else:
                raise ValueError(f"unknown distribution {c['dist']!r}")
        return out

    out = gen(jax_key(seed, f"table:{table}"))
    return {n: out[n] for n in column_names(spec)}

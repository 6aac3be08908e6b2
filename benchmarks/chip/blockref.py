"""The plain reference over a collection held as one block per chip:
each block scanned whole on its own chip (``refknn.brute_topk``), its
row indices offset by the rows of the blocks before it, and the chips'
top k merged by (distance, id).  Nothing here imports the program.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import refknn


def blocks_topk(blocks: Iterable[jax.Array], queries: np.ndarray, *,
                k: int, qblock: int = 16, dtype=jnp.float32
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact k-NN of ``queries`` over the rows of ``blocks`` taken in
    order, ``qblock`` queries per pass: (squared distances ``[Q, k]``
    float32, global row indices ``[Q, k]`` int64), ties to the lower
    index.  ``blocks`` may be a generator: each block is scanned and
    let go before the next is drawn."""
    queries = np.asarray(queries, np.float32)
    nq = len(queries)
    qs = np.concatenate([queries,
                         np.repeat(queries[-1:], -nq % qblock, 0)])
    out_d, out_i, base = [], [], 0
    for b in blocks:
        dev = next(iter(b.devices()))
        b = jax.device_put(b, dev)     # committed: the scan runs there
        d, i = [], []
        for s in range(0, len(qs), qblock):
            bd, bi = refknn.brute_topk(
                b, jax.device_put(qs[s:s + qblock], dev), k=k,
                dtype=dtype)
            d.append(np.asarray(bd))
            i.append(np.asarray(bi, np.int64) + base)
        out_d.append(np.concatenate(d)[:nq])
        out_i.append(np.concatenate(i)[:nq])
        base += int(b.shape[0])
        del b
    d = np.concatenate(out_d, axis=1)
    i = np.concatenate(out_i, axis=1)
    order = np.lexsort((i, d), axis=1)[:, :k]
    return (np.take_along_axis(d, order, 1),
            np.take_along_axis(i, order, 1))

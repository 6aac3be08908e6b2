"""A toy configuration kind: its "system" scans every row of each block
of the collection on the block's own device (the plain reference's
scan, ``refknn.brute_topk``) and merges the blocks' top k by (distance,
id).  Its control runs the same scan in bfloat16."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

import refknn


class BruteBlocks:
    dtype = jnp.float32

    def __init__(self, config: dict):
        self.config = config

    def search(self, blocks, queries: np.ndarray, *, k: int
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact k-NN over every block: each block's top k on its own
        device, merged on the host, ties to the lower id."""
        parts, base = [], 0
        for b in blocks:
            q = jax.device_put(np.asarray(queries, np.float32),
                               b.devices().pop())
            d, i = refknn.brute_topk(b, q, k=k, dtype=self.dtype)
            parts.append((d, i + base))
            base += b.shape[0]
        d = np.concatenate([np.asarray(p[0]) for p in parts], axis=1)
        i = np.concatenate([np.asarray(p[1]) for p in parts], axis=1)
        order = np.lexsort((i, d), axis=1)[:, :k]
        return (np.take_along_axis(d, order, 1),
                np.take_along_axis(i, order, 1).astype(np.int64))


class BruteBlocksBf16(BruteBlocks):
    dtype = jnp.bfloat16


def system(config: dict) -> BruteBlocks:
    return BruteBlocks(config)


def control(config: dict, traffic: dict) -> BruteBlocks:
    return BruteBlocksBf16(config)


def cpu_size(config: dict, traffic: dict) -> Tuple[dict, dict]:
    return dict(config, series=1 << 13), dict(traffic, pool_calls=4)

"""What the selection entries share: a pool of arrays, ranks, and the
plain reference, ``np.partition`` of each array on the host.

An entry module builds an :class:`ArrayEntry` from its program and the
ranks it asks for.  The harness drives the attributes below; see
``bench/run.py``.
"""
from __future__ import annotations

import concurrent.futures
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate


def ranks_of(qs, n):
    """``ceil(q n)`` clipped to ``[1, n]``, in f64 on the host."""
    return np.clip(np.ceil(np.asarray(qs, np.float64) * n), 1, n).astype(
        np.int64)


def reference(x: np.ndarray, ranks) -> np.ndarray:
    """The k-th smallest elements of ``x`` for each k of ``ranks``;
    partitions ``x`` in place."""
    r = np.asarray(ranks, np.int64) - 1
    x.partition(r)
    return x[r]


def _bits(v) -> np.ndarray:
    return np.asarray(v, np.float32).reshape(-1).view(np.uint32)


class ArrayEntry:
    """One selection program over a pool of ``n``-element arrays.

    ``program(x)`` returns a ``SelectResult`` whose ``value`` holds one
    answer per rank of ``ranks``.
    """

    def __init__(self, cfg, mix, seed, program, ranks, sharding=None):
        self.ranks = np.asarray(ranks, np.int64).reshape(-1)
        self.pool = generate.array_pool(seed, int(cfg["n"]), cfg["dtype"],
                                        mix["pool"], sharding)
        self.fn = jax.jit(program)
        self._program = program
        self.sharded = sharding is not None

    def args(self, i, call):
        return (self.pool[i],)

    @staticmethod
    def sweeps(out):
        """Histogram sweeps of one call: the loop runs until its last rank
        is done."""
        return int(np.max(np.asarray(out.iters)))

    def baseline(self):
        """``jnp.sort`` of one array of the pool, the sort a user would
        otherwise run, as a jitted function and its argument; None for a
        sharded array, whose sort would gather it whole onto each chip."""
        if self.sharded:
            return None
        return jax.jit(jnp.sort), (self.pool[0],)

    @contextlib.contextmanager
    def control(self):
        """The control: the program served bf16 data, the answer cast back
        to f32 (the step that would halve the bytes read)."""
        prog = self._program

        def control(x):
            res = prog(x.astype(jnp.bfloat16))
            return res._replace(value=res.value.astype(jnp.float32))

        self.fn = jax.jit(control)
        try:
            yield
        finally:
            self.fn = jax.jit(prog)

    def check(self, calls):
        """Compare every answer of ``calls`` (``(pool index, output)``
        pairs) with ``np.partition`` of its array, bit for bit.

        Returns ``(failed_calls, {number: (value, limit)})``."""
        used = sorted({i for i, _ in calls})

        def ref(i):
            return i, reference(np.array(self.pool[i]), self.ranks)

        with concurrent.futures.ThreadPoolExecutor(len(used)) as ex:
            want = dict(ex.map(ref, used))
        wrong = failed = 0
        for i, out in calls:
            bad = int(np.sum(_bits(out.value) != _bits(want[i])))
            wrong += bad
            failed += bad > 0
        return failed, {"wrong_answers": (wrong, 0)}

"""Weights made from a seed when they are asked for, and the decoder that
holds them: one copy.

Every served decoder of a published configuration (``models/experts.py``'s
four, ``models/ouro.py``) is too large to build on the host as one tree: a
leaf is a :class:`SeededWeight`, made, put on the device and let go
(:class:`SeededDecoder` ``place_params``), and a reference that asks for
float32 holds exactly the bfloat16-rounded values the chip holds.
"""

from __future__ import annotations

import concurrent.futures
import math
import os

import numpy as np

from client_tpu.models.decoder import DecoderBackend

_CHUNK = 1 << 24          # elements of a weight made by one task
_BLOCK = 1 << 17          # elements made at a time (cache-sized)


class SeededWeight:
    """A weight that is made when it is asked for: ``offset + scale * N(0,
    1)`` from its own seed, **rounded to bfloat16** whatever dtype it is asked
    in, so a reference that asks for float32 (``np.asarray(w, np.float32)``)
    holds exactly what the chip holds and never a second copy.  Chunks of
    ``_CHUNK`` elements have seeds of their own and are filled by as many
    threads as the process may use (numpy's generators release the
    interpreter lock): the values do not depend on the thread count.  With
    ``first`` given, entry i of the leading axis is made from ``first + i``
    alone: the experts a share holds are the model's, whichever share holds
    them."""

    def __init__(self, seed, shape, scale, offset=0.0, dtype="bfloat16",
                 first=None):
        self.seed, self.shape = tuple(int(s) for s in seed), tuple(shape)
        self.scale, self.offset = float(scale), float(offset)
        self.dtype = str(dtype)          # "bfloat16" | "float32"
        self.first = first

    def _spans(self):
        """(lo, hi, seed) of every chunk of the flattened weight."""
        n = int(np.prod(self.shape))
        unit = n if self.first is None else n // self.shape[0]
        return [(u + lo, u + min(lo + _CHUNK, unit),
                 [*self.seed, lo // _CHUNK] + (
                     [] if self.first is None else [self.first + u // unit]))
                for u in range(0, n, unit) for lo in range(0, unit, _CHUNK)]

    def _fill(self, out, lo, hi, seed):
        """Chunk ``[lo, hi)`` of the flattened weight into ``out`` (float32,
        or uint16 holding bfloat16's bits), a block at a time and in place:
        whole-chunk temporaries would be mapped and unmapped by every thread
        at once, which the kernel serializes."""
        rng = np.random.default_rng(seed)
        wide = out.dtype == np.float32
        scratch = None if wide else np.empty(_BLOCK, np.float32)
        carry = np.empty(_BLOCK, np.uint32)
        for a in range(lo, hi, _BLOCK):
            b = min(a + _BLOCK, hi)
            part = out[a:b] if wide else scratch[:b - a]
            rng.standard_normal(b - a, dtype=np.float32, out=part)
            part *= np.float32(self.scale)
            if self.offset:
                part += np.float32(self.offset)
            bits, t = part.view(np.uint32), carry[:b - a]
            np.right_shift(bits, 16, out=t)      # round to nearest even
            t &= np.uint32(1)
            t += np.uint32(0x7FFF)
            bits += t
            if wide:
                bits &= np.uint32(0xFFFF0000)
            else:
                np.right_shift(bits, 16, out=t)
                out[a:b] = t

    def __array__(self, dtype=None, copy=None):
        import ml_dtypes

        wide = self.dtype == "float32" or (
            dtype is not None and np.dtype(dtype) == np.float32)
        out = np.empty(int(np.prod(self.shape)),
                       np.float32 if wide else np.uint16)
        spans = self._spans()
        workers = max(1, min(len(spans), len(os.sched_getaffinity(0))))
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            list(pool.map(lambda s: self._fill(out, *s), spans))
        out = out.reshape(self.shape)
        return out if wide else out.view(ml_dtypes.bfloat16)


class SeededDecoder(DecoderBackend):
    """A decoder whose tree is ``SeededWeight`` leaves; a model sets ``dtype``
    and ``_seed``."""

    def _weight_makers(self):
        """``w(*shape, scale, ...)``, ``mat(rows, cols)`` and ``gain(n)``:
        ``SeededWeight`` leaves numbered in the order they are asked for.  A
        float32 model's weights are still rounded to bfloat16 values: the
        same numbers in both forms of the program."""
        count = iter(range(1 << 20))

        def w(*shape, scale, offset=0.0, dtype=None, first=None):
            return SeededWeight((self._seed, next(count)), shape, scale,
                                offset, dtype or self.dtype, first)

        def mat(rows, cols):
            return w(rows, cols, scale=1.0 / math.sqrt(rows))

        def gain(n):
            return w(n, scale=0.1, offset=1.0)

        return w, mat, gain

    def place_params(self, params):
        """Leaf by leaf: a weight is made, put on the device and let go, so
        the host never holds the model."""
        import jax

        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(np.asarray(leaf)), params)

    def _mm(self, x, w):
        """Operands in the weights' dtype, float32 result."""
        import jax.numpy as jnp

        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

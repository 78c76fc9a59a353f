"""Seeded random tensors that the device and the host can both recompute.

Every value is a hash of (seed, stream, index): a murmur-style mixer over
uint32 counters, so any slice (one layer of a stacked weight) can be made
alone and comes out bit for bit the same in whatever program computes it.
The benchmark makes its weights and payloads with it; the references
recompute them from the seed instead of reading the program's arrays.

Values are uniform on [-a, a) plus `mean`, with `a` the power of two
nearest to std * sqrt(3) (so the standard deviation is within a factor of
sqrt(2) of `std`).  A power-of-two scale makes the product exact, so the
result has one rounding, in the add, however a compiler fuses the two.
Uniform draws are cheap on the chip (a few integer operations per value,
where a normal draw costs a threefry block) and serve as well for speed and
for agreement with a reference.
"""

from __future__ import annotations

import math
import zlib

import numpy as np

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35
_SQRT3 = 3.0 ** 0.5


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of any size as two uint32 words (low, high)."""
    s = int(seed) & ((1 << 64) - 1)
    return s & 0xFFFFFFFF, s >> 32


def stream_id(name: str, index: int = 0) -> int:
    """A stream number for a named tensor (and e.g. its layer)."""
    return (zlib.crc32(name.encode()) + 0x632BE5AB * int(index)) & 0xFFFFFFFF


def _mix(xp, i, k0, k1):
    u32 = xp.uint32
    x = i * u32(_M1) + k0
    x = x ^ (x >> u32(16))
    x = x * u32(_M2)
    x = x ^ (x >> u32(13))
    x = x + k1
    x = x * u32(_M3)
    x = x ^ (x >> u32(16))
    x = x * u32(_M2)
    x = x ^ (x >> u32(15))
    return x


def scale(std: float) -> float:
    """The step between neighbouring values: a power of two times 2**-23."""
    return 2.0 ** (round(math.log2(std * _SQRT3)) - 23)


def uniform_jnp(seed: int, stream, shape, std: float, mean: float = 0.0):
    """float32 tensor; `stream` may be a traced uint32."""
    import jax
    import jax.numpy as jnp
    lo, hi = seed_words(seed)
    stream = jnp.asarray(stream, jnp.uint32)
    k0 = jnp.uint32(lo) ^ stream
    k1 = jnp.uint32(hi) + jnp.uint32(0x7F4A7C15) * ((stream >> 7) | jnp.uint32(1))
    n = int(np.prod(shape, dtype=np.int64))
    i = jax.lax.iota(jnp.uint32, n)
    x = _mix(jnp, i, k0, k1)
    q = (x >> jnp.uint32(8)).astype(jnp.int32) - jnp.int32(1 << 23)
    v = q.astype(jnp.float32) * jnp.float32(scale(std))
    return (v + jnp.float32(mean)).reshape(shape)

"""DGEMM through ``api.multiply`` on one chip, compiled whole with
``jax.jit``: C = 1.0 A B in float64, which the dispatch
(``ops/matmul.matmul``) sends through the int8 Ozaki path on a TPU.  The
program opens ``ops.ozaki.slice_record`` around the call inside its jit
and returns the slice count the products ran beside C, from the same
trace: the call returns (((C_hi, C_lo), S), None), S = 0 where the
product took another path.

C leaves the chip as its float32 pair C_hi = f32(C), C_lo = f32(C - C_hi)
(``operations/gemm.py`` adds them on the host).  A TPU holds float64 as
such a pair, so on the chip the pair is C exactly (bit-equal to the
runtime's own float64 copy, which took 2.5 s for 512 MiB against 0.2 s
for the pair on a v5e); elsewhere it keeps 48 of the 53 bits.

On a TPU host the process keeps the host memory it frees: C's pair lands
in 512 MiB of fresh pages every call, and glibc hands blocks that large
back to the kernel on free, so each copy of C also paid for faulting in
131,072 pages: on a v5e host the copy took 0.17 s, and 0.047 s into
kept memory, and the faults were the part of a call's time that moved
from run to run.  The heap is also faulted in once, at set-up, to
``RESIDENT_BYTES``: the rows a run keeps from each call can leave a
hole too small for the next pair, and a pair placed past the heap's end
would fault in its pages again.  Off a TPU the allocator is left as it
is.

A float64 user of JAX turns 64-bit mode on before making any array, and
so does this module, as it is imported.  Each cell runs in a process of
its own."""

import ctypes

import jax
from jax.sharding import SingleDeviceSharding

jax.config.update("jax_enable_x64", True)


def shardings(traffic, devices):
    one = SingleDeviceSharding(devices[0])
    return one, one


_M_TRIM_THRESHOLD, _M_MMAP_MAX = -1, -4  # glibc's mallopt parameters
_NO_TRIM = 2**31 - 1  # the largest threshold mallopt takes
# C's pair (512 MiB at n 8192), a window's kept rows (4 MiB a call) and
# room for holes; under _NO_TRIM, so the heap's free top stays resident
RESIDENT_BYTES = 3 << 29


def keep_host_memory() -> bool:
    """glibc's malloc serves every block from its heap (no block of its own
    mapped and unmapped per allocation) and trims the heap only past
    ``_NO_TRIM``, so the pages a freed host copy of C held serve the next
    one; then ``RESIDENT_BYTES`` of heap are faulted in and freed.  False
    where the C library is not glibc."""
    libc = ctypes.CDLL(None)
    mallopt = getattr(libc, "mallopt", None)
    if mallopt is None or not (mallopt(_M_MMAP_MAX, 0) and mallopt(_M_TRIM_THRESHOLD, _NO_TRIM)):
        return False
    libc.malloc.argtypes, libc.malloc.restype = [ctypes.c_size_t], ctypes.c_void_p
    libc.free.argtypes = [ctypes.c_void_p]
    block = libc.malloc(RESIDENT_BYTES)
    if block:
        ctypes.memset(block, 0, RESIDENT_BYTES)
        libc.free(block)
    return True


def build(traffic, devices):
    import jax.numpy as jnp

    from slate_tpu import api
    from slate_tpu.ops.ozaki import slice_record

    def body(a, b):
        with slice_record() as counts:
            c = api.multiply(1.0, a, b)
        hi = c.astype(jnp.float32)
        return (hi, (c - hi.astype(c.dtype)).astype(jnp.float32)), \
            counts[-1] if counts else jnp.int32(0)

    if devices[0].platform == "tpu":
        keep_host_memory()
    multiply = jax.jit(body)
    return lambda a, b: (multiply(a, b), None)

"""The host allocator setting that every run of the benchmark makes first.

In every admission round the server copies its whole adjacency to the host
(512 MiB at 2^16 vertices) for the epoch ring and frees the copy before.
glibc hands blocks that large back to the kernel when they are freed and
maps fresh pages for the next, so each round pays a first touch of every
page. On a TPU v5e host a first touch of 128 MiB took about 140 ms against
17 ms for a copy into memory already touched, and the same program's
rounds took 51 ms in one process and 74 ms in the next.
``keep_freed_memory`` makes glibc serve every block from its heap and keep
what is freed there for the next allocation, as a long-running server's
allocator does, so that a run measures the program's own host work.
"""
import ctypes

M_TRIM_THRESHOLD = -1
M_MMAP_MAX = -4


def keep_freed_memory() -> bool:
    """Call before the first large allocation; True where glibc took it."""
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        return False
    no_mmap = libc.mallopt(M_MMAP_MAX, 0) == 1
    keep = libc.mallopt(M_TRIM_THRESHOLD, 1 << 30) == 1
    return no_mmap and keep

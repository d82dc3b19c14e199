"""The run's allocator settings take, and a call's worth of host arrays,
freed and asked for again, comes back from the kept heap."""
import subprocess
import sys

import _chipbench_path  # noqa: F401
from chipbench import host

_PROBE = r"""
import resource, sys
sys.path.insert(0, sys.argv[1])
from chipbench.host import fix_allocator
if sys.argv[2] == "1":
    assert fix_allocator()
import numpy as np
a = np.ones(4 << 20, np.uint8)   # freed: glibc's mmap threshold rises
del a                            # to 4 MiB, its trim threshold to 8 MiB
f0 = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
for _ in range(10):              # a "call": six 4 MiB results, then freed
    out = [np.ones(4 << 20, np.uint8) for _ in range(6)]
    del out
print(resource.getrusage(resource.RUSAGE_SELF).ru_minflt - f0)
"""


def test_fix_allocator_takes():
    assert host.fix_allocator() is True


def _faults(fix: bool) -> int:
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, str(_chipbench_path.BENCH),
         "1" if fix else "0"], capture_output=True, text=True, check=True)
    return int(out.stdout.split()[-1])


def test_fixed_allocator_keeps_the_heap():
    """Ten calls of six 4 MiB arrays (6,144 pages a call): glibc's own
    thresholds give much of the heap back after a call and fault it in
    again in the next; pinned, the first call's pages are reused."""
    unfixed, fixed = _faults(False), _faults(True)
    assert fixed < 6144
    assert unfixed > 3 * 6144

"""What the host gave a run, beside what the run did with it: a record in
``result.<group>.json`` (``host_probe``), taken by each worker just before its
window opens and just after it closes. A step of the FT loop is paced by the
host (a copy of 2.43 GB and the runtime's threads); when a whole run steps
slower, the probe says whether a fixed piece of host work was slower too.

* ``copy_s``: the median of three ``np.copyto`` of a fixed 256 MB array into a
  buffer that has been touched once (the buffers are filled by a thread while
  the backend comes up, so their page faults are not on the set-up's path);
* ``spin_s``: a fixed NumPy-free loop of 2e6 turns on the calling thread.

Both together cost 0.08 s of ``setup_s``. Not a metric: the one reader is
``layer_metrics/host_probe_copy_s.py``. PR 29 also read the kernel's view at
both instants (cgroup ``cpu.stat``, ``smaps_rollup``, ``numa_maps``,
``/proc/pressure``, the processes of earlier runs): the machine with the chip
exposes none of the first four, and no earlier run's process was ever alive;
that part is gone again (PERF.md section 6).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict

COPY_BYTES = 256 << 20
COPIES = 3
SPIN_TURNS = 2_000_000


class HostProbe:
    """``HostProbe()`` once the worker has NumPy, ``take()`` at each instant."""

    def __init__(self) -> None:
        self._src = self._dst = None
        self._filler = threading.Thread(target=self._fill, daemon=True)
        self._filler.start()

    def _fill(self) -> None:
        import numpy as np

        self._src = np.ones(COPY_BYTES, np.uint8)
        self._dst = np.empty_like(self._src)
        np.copyto(self._dst, self._src)  # touched once

    def take(self) -> Dict[str, Any]:
        import numpy as np

        self._filler.join()
        t_begin = time.monotonic()
        copies = []
        for _ in range(COPIES):
            t0 = time.perf_counter()
            np.copyto(self._dst, self._src)
            copies.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for _ in range(SPIN_TURNS):
            pass
        spin = time.perf_counter() - t0
        return {"t": t_begin, "copy_s": sorted(copies)[COPIES // 2], "spin_s": spin, "cost_s": time.monotonic() - t_begin}

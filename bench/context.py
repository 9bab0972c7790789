"""What a metric reader is given: the run's calls, timings, counts and the
reduced trace. Readers take what they need and return None where the run
holds nothing for them."""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from bench.load import Call, Step
from bench.trace_reduce import Reduction


@dataclasses.dataclass
class Ctx:
    config: dict                 # bench/configs/<config>.json
    mix: dict                    # bench/traffic/<traffic>.json
    calls: List[Call]            # the window's entry calls, in order
    setup_s: float               # process start to the window's start
    build_s: float               # the index build
    warmup_s: float              # the one warm-up call of the entry
    recall: float                # Recall@10 over every served request
    page_bytes: int              # the index's page size
    pq_m: int                    # PQ sub-quantizers (bytes per code)
    red: Optional[Reduction] = None   # the trace, in a --trace 1 run
    peaks: Optional[dict] = None      # bench/peaks.json entry of the chip
    steps: List[Step] = dataclasses.field(default_factory=list)
    #                                   the window's mutation steps (stream)

    def per_request(self, field: str) -> np.ndarray:
        return np.concatenate([np.asarray(c.out[field]) for c in self.calls])

    @property
    def requests(self) -> int:
        return sum(len(c.pool_idx) for c in self.calls)

    @property
    def window_s(self) -> float:
        """From the start of the first call to the end of the last."""
        return self.calls[-1].end - self.calls[0].start

    def program(self, name: str) -> Optional[dict]:
        """{count, device_s} of a jitted program in the trace, or None."""
        if self.red is None:
            return None
        p = self.red.programs.get(name)
        return p if p and p["count"] else None

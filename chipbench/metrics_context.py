"""What a per-layer metric's reader is given: the reduced trace of the
traced window, the kernel calls seen in it, the served tokens with their
host times, the cell's architecture module and the chip's peaks. A
reader returns a number, or None where it finds nothing to read."""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple


@dataclasses.dataclass
class Context:
    trace: object  # chipbench.trace.Trace
    window_ns: Optional[Tuple[float, float]]  # the window on the trace's clock
    window_s: float  # the traced window on the host clock
    cfg: object  # the program's ModelConfig of the cell
    arch: object  # the cell's architecture module (bench.architecture)
    peaks: dict  # chipbench/peaks.json entry of the device kind
    #: per kv_restore call: ((n, K, hd), page itemsize, token itemsize,
    #: scale itemsize)
    restore_calls: List[tuple]
    #: per paged_attention call: ((B, H, hd), (P, ps, K, hd), page
    #: itemsize, context_lens as numpy)
    attend_calls: List[tuple]
    clients: object  # chipbench.run.Clients after the window
    t0: float  # window start, host clock
    t_stop: float  # end of the traced part, host clock
    compiles: int  # backend compiles inside the window

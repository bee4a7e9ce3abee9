"""The largest ``memory_stats()["peak_bytes_in_use"]`` over the cell's
devices at the end of the window, in GiB.

The peak is the process's, so it includes the check's readouts in set-up
(each group's live rows and first moments read back by key, and the
change norms' chunks), which run between steps beside the whole state. In
the dlrm-mlperf 4-chip cell on TPU v5e the peak read 8,245,097,984 bytes
after the first step and 8,321,273,344 once that step's readouts had run
(76 MB more); a run's peak after three checked steps and the window is
8,419,503,104. The run prints ``window.peak_bytes_step1``, the peak before
any readout, beside it."""


def read(ctx):
    return ctx.peak_bytes / 2**30 if ctx.peak_bytes else None

"""Share of the chip's bf16 peak that serving reached: the FLOPs that the
logits of every request answered in the traced window need
(``bench/lib/counts.py``), over the window's length in the trace (first
request span's start to the last one's end) times the peak.  Moves
``prompt_tokens_per_s``."""


def read(run):
    flops, window = run.counters.get("flops"), run.summary["window_s"]
    if not flops or window <= 0:
        return None
    return 100.0 * flops / (window * run.peaks["bf16_flops_per_s"])

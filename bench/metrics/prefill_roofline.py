"""Share of the roofline that serving reached: the least time the chip
could have answered every request of the traced window in (per stage of
each prefill, the larger of its FLOPs over the bf16 peak and the weight
bytes it must read over HBM bandwidth, ``bench/lib/counts_mla_moe.py``),
over the window's length in the trace.  Where the step mixes bound kinds,
as bandwidth-bound experts beside compute-bound attention and head, this is
the yardstick ``prefill_mfu`` understates.  Moves ``prompt_tokens_per_s``."""


def read(run):
    roof, window = run.counters.get("roofline_s"), run.summary["window_s"]
    if not roof or window <= 0:
        return None
    return 100.0 * roof / window

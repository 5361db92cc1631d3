"""wave_yield_pct (%): the share of the candidate slots the wave loop put
on the device that became answer itemsets: 100 * the itemsets of two or
more items in the answers / ``stage_times_s["wave_slots"]`` (every wave's
padded slot count), both summed over the window's mine requests and
stream queries."""


def read(run):
    found = slots = 0
    for op in run.ops:
        if op.kind not in ("mine", "query") or op.error is not None:
            continue
        if "wave_slots" not in op.result.stage_times_s:
            continue
        slots += op.result.stage_times_s["wave_slots"]
        found += sum(len(s) >= 2 for s in op.result.itemsets)
    return 100.0 * found / slots if slots else None

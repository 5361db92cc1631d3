"""nlist_fill_pct (%): the share of the packed N-list slots that hold a
node: 100 * ``stage_times_s["nlist_nodes"]`` (the nodes of every data
shard's PPC-tree) / ``stage_times_s["nlist_slots"]`` (D x K x W, what
every wave's kernel reads), both summed over the window's mine
requests."""


def read(run):
    nodes = slots = 0
    for op in run.ops:
        if op.kind != "mine" or op.error is not None:
            continue
        stages = op.result.stage_times_s
        if "nlist_slots" not in stages:
            continue
        nodes += stages["nlist_nodes"]
        slots += stages["nlist_slots"]
    return 100.0 * nodes / slots if slots else None

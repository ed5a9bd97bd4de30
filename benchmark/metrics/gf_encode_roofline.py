"""Percent of the HBM roofline the encode reached while the device was
busy: the least bytes an RS(k, r) encode of the window's jobs moves
(their `.dat` bytes read once, r/k of them written once) at the peaks
table's bytes/s, over the union of device operation time.  Bandwidth
bounds it; on a cell of several chips the peak is theirs together and
the busy time their mean.  From the volumes' bytes as the harness
loaded them and busy time: no counter of the program (its own count of
bytes launched includes padding: `staging_launch_ratio`) and no
operation name."""

from benchmark import trace_reduce


def read(ctx):
    dat = sum(j["bytes"] for j in ctx["jobs"] if j["ok"])
    if not ctx["trace"] or dat <= 0:
        return None
    cfg = ctx["cfg"]
    least = trace_reduce.encode_min_bytes(
        dat, cfg["data_shards"], cfg["parity_shards"])
    return trace_reduce.roofline_share(
        least, ctx["trace"]["busy_s"], ctx["device"]["kind"],
        ctx.get("chips", 1))

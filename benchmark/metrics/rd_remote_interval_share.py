"""Share of the needle intervals read through the EC path that came
from another server's shard: `remote` over all of
`ec_read_intervals_total{source}`, summed over the volume roles'
`/metrics`.  The counter runs from a role's start, so this is the
share over the run: the clients' reads of the window, the load
children's warm-up reads, and the needles the comparison reads back
from every timed volume after the window (66 a volume here, about two
intervals each), which the run prints beside it with what a fetch
from another server took (`ec_remote_read_seconds`)."""

from benchmark import role_metrics


def read(ctx):
    by = role_metrics.by_label(ctx, "volume", "ec_read_intervals_total",
                               "source")
    if not by or sum(by.values()) <= 0:
        return None
    hop = role_metrics.histogram(ctx, "volume", "ec_remote_read_seconds")
    print(f"  ec read intervals over the run: "
          f"{ {k: int(v) for k, v in sorted(by.items())} }; "
          f"{ctx['reads']['requests'] if ctx.get('reads') else 0} of the "
          "reads were the window's"
          + (f"; a fetch from another server took "
             f"{hop['sum'] / hop['count'] * 1e3:.2f} ms in the mean, the "
             f"slowest under {hop['slowest_le']} s" if hop else ""),
          flush=True)
    return by.get("remote", 0.0) / sum(by.values())

"""How near the run came to the fault that misplaces shards: the
volume roles' heartbeats that raised or did not reach the master
(`volume_heartbeat_errors_total`, every `error` summed) plus the
volume servers the master let go of (`master_node_transitions_total
{to="dead"}`), over the run.  0 in a sound run.  What the heartbeats
cost is printed beside it, for the look at why one would be late, and
the seconds the master itself stood still (`master_own_stall_seconds_
total`: the machine stops for some seconds when the chip's owner
initialises), which it does not hold against the servers; and what
the jobs' `ec.distribute` spans say of their placement: the servers
at the start, the servers placed on, and who waited."""

from benchmark import job_trace, role_metrics


def read(ctx):
    errors = role_metrics.by_label(
        ctx, "volume", "volume_heartbeat_errors_total", "error")
    moves = role_metrics.by_label(
        ctx, "master", "master_node_transitions_total", "to")
    beats = role_metrics.histogram(ctx, "volume", "volume_heartbeat_seconds")
    if beats is None:
        return None         # the program has no such counter
    stalled = role_metrics.by_label(
        ctx, "master", "master_own_stall_seconds_total", "")
    print(f"  heartbeats over the run: {int(beats['count'])} timed, mean "
          f"{beats['sum'] / beats['count'] * 1e3:.2f} ms, the slowest "
          f"under {beats['slowest_le']} s; errors {errors or {}}; the "
          f"master's transitions {moves or {}}; the master itself stood "
          f"still {sum((stalled or {}).values()):.1f} s", flush=True)
    # the other sign that it came near: a job that found the master
    # naming fewer servers than it started under, and waited for them
    spread = [s.get("attrs") or {} for s in job_trace.named(
        ctx, "ec.distribute")]
    print(f"  the window's jobs placed on (serversAtStart, servers) "
          f"{sorted({(a.get('serversAtStart'), a.get('servers')) for a in spread})}"
          f"; {sum('waitSeconds' in a for a in spread)} of {len(spread)} "
          "waited for a server" + "".join(
              f" ({a['waitSeconds']} s)" for a in spread
              if "waitSeconds" in a), flush=True)
    return sum((errors or {}).values()) + (moves or {}).get("dead", 0.0)

"""Schedule build: mean time of ``make_source`` per frame, in ms (host clock,
the benchmark's span around the call).  Host-path frames only."""


def read(run):
    times = [f.source_build_s for f in run.frames if hasattr(f, "source_build_s")]
    return 1e3 * sum(times) / len(times) if times else None

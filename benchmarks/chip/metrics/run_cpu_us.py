"""Dispatch and execute: mean CPU time of a worker's thread inside the loop
body per chunk, in us, over the chunks of the traced frames (``cpu_s`` of
the executor's records).  Beside ``chunk_exec_us``, the wall time of the
same call, the rest is time the worker waited: for the interpreter lock or
the device.  Only frames run with the program's tracing on record it."""


def read(run):
    cpu = [r.cpu_s for f in run.frames for r in getattr(f, "records", ())
           if getattr(r, "cpu_s", None) is not None]
    return 1e6 * sum(cpu) / len(cpu) if cpu else None

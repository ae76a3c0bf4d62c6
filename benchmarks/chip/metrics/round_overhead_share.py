"""SPMD rounds: the share of the chips' summed device busy time in the
traced window that lies outside the body's kernel (``mandelbrot_tile``):
DCA's cost on the device.  On a v5e the kernel's fusion also writes the
round's result into the frame's output, so the rest is the closed-form
schedule and each round's slicing of its (offset, size).  Busy time is the
union of a chip's op intervals, so the ``while`` that spans the rounds'
ops counts once; the kernel's time is its events' own.  Cells of two or
more chips."""

KERNEL = "mandelbrot_tile"


def read(run):
    busy = sum(run.trace["busy_s"])
    kernel = sum(t for name, t in run.trace["device_ops"] if name == KERNEL)
    if run.chips < 2 or not busy or not kernel:
        return None
    return 1.0 - kernel / busy

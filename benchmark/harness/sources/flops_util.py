"""Model FLOP/s utilisation: rows a second times the matrix-product FLOPs
a row requires (the adapter's `flops_per_example`: forward and backward,
nothing recomputed), over the chip's bf16 peak. It follows from the run's
rate by a constant of the cell; an end-to-end utilisation, not a kernel's
roofline share. No args."""

from benchmark.harness import peaks


def read(args: dict, r: dict):
    cell = r["cell"]
    flops = r["adapter"].flops_per_example(cell["config"], cell["traffic"])
    return 100.0 * r["examples_per_s"] * flops / peaks.peak(
        r["device_kind"], "bf16_flops")

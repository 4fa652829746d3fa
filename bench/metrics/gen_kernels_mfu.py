"""The kernel suite's FLOPs over its programs' device time and the peak,
in %: what the generated code achieves of the chip as a whole."""

from bench.cells import load_file_module


def read(t, rec, peak):
    flops = seconds = 0.0
    for k in rec["kernels"]:
        runs, s = t.program(k.program)
        if runs == 0:
            return None
        flops += load_file_module("work", k.name).call(k.spec)[0] * runs
        seconds += s
    return 100.0 * flops / seconds / peak["flops_per_s"]

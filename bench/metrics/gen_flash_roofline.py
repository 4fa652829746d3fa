"""The generated flash attention's share of its roofline, in %: the
algorithm's work (``bench/work/gen_flash.py``; bound by bandwidth) over
the device time of all its Mosaic kernels (one per nest)."""


def read(t, rec, peak):
    from bench.metrics import kernel_roofline
    return kernel_roofline(t, rec, peak, "gen_flash")

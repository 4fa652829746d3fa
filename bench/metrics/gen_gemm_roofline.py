"""The generated GEMM's share of its roofline, in % (``bench/work/
gen_gemm.py``; bound by compute at the cell's shape)."""


def read(t, rec, peak):
    from bench.metrics import kernel_roofline
    return kernel_roofline(t, rec, peak, "gen_gemm")

"""Device time of one batched decode step (``_batched_step``), in ms."""


def read(t, rec, peak):
    from bench.metrics import per_run_ms
    return per_run_ms(t, ["_batched_step"], "_batched_step")

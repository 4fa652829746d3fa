"""Device time of one admission: its prefill (``_prefill``) and the write
of its cache into a slot (``_write``), in ms per request admitted."""


def read(t, rec, peak):
    from bench.metrics import per_run_ms
    return per_run_ms(t, ["_prefill", "_write"], "_prefill")

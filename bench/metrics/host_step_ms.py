"""The host's own work in one engine step, in ms: ``serve.step`` less
its ``serve.sync`` and ``serve.first_token`` waits (the active mask,
admissions' dispatch, the decode dispatch and the per-slot emit loop),
read from the traced slices' host spans."""


def read(t, rec, peak):
    from bench.spans import host_step_ms
    return host_step_ms(t.spans)

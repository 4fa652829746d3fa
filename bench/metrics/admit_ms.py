"""Mean length of one admission, in ms: the engine's ``serve.admit``
span (the B=1 prefill's dispatch, the host's wait for its first token
and the write into a slot), read from the traced slices' host spans.
Every slot waits while it runs."""


def read(t, rec, peak):
    from bench.spans import admit_ms
    return admit_ms(t.spans)

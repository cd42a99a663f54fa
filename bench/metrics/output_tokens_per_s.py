"""output_tokens_per_s: every token the clients received in the window,
over the window's length."""


def read(rec):
    n = sum(1 for r in rec.requests for t in r.recv if rec.in_window(t))
    return n / rec.seconds

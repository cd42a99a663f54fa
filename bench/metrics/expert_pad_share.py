"""expert_pad_share: the share of the expert-FFN rows the window's
programs computed that no token was routed to: 100 x (1 - routed rows
/ computed rows), summed over the engine ticks in the window
(``engine.last_step["routed_rows"]`` and ``["expert_rows"]``, as
``Step.routed_rows`` and ``Step.expert_rows``).  A dropless dispatch
that queues every token at every expert computes ``n_experts / top_k``
times the routed rows, so this reads 1 - top_k / n_experts and more
(idle slots, padded prefill buckets).  Reads nothing from a record
that carries no such counter, or a dense model's."""


def read(rec):
    steps = [s for s in rec.steps if rec.in_window(s.t0)]
    computed = sum(getattr(s, "expert_rows", 0) or 0 for s in steps)
    routed = sum(getattr(s, "routed_rows", 0) or 0 for s in steps)
    if computed <= 0:
        return None
    return 100.0 * (1.0 - routed / computed)

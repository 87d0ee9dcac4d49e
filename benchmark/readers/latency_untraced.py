"""What the spans leave of the client's latency, at quantile ``q``: per
registration due in the window, ``done - due`` less the union, on one
clock, of ``due -> sent`` (the generator running late) and every span of
that registration's eval, from ``register`` (entry of ``register_job``) to
the status flush and the ack. What is left is the client's own wake-up
after the commit plus whatever interval of the program no span covers. The
client's stamps are moved onto the spans' clock by the tracer itself
(``unix_at``). Returns nothing where the program writes no ``register``
span (a program older than the pass record)."""

from benchmark.readers.pass_wall import end, union_s
from benchmark.spans import quantile


def read(ctx, q):
    from nomad_tpu.obs.trace import global_tracer

    unix_at = getattr(global_tracer, "unix_at", None)
    by_eval = {t.get("eval_id"): t for t in ctx["traces"]}
    values = []
    for r in ctx["registers"]:
        spans = by_eval.get(r.eval_id, {}).get("spans", ())
        if unix_at is None or not r.ok or not any(
            s.get("name") == "register" for s in spans
        ):
            continue
        due, done = unix_at(r.due), unix_at(r.done)
        covered = [(due, unix_at(r.sent))] + [
            (max(s["start_unix"], due), min(end(s), done))
            for s in spans if s.get("parent_id") is not None
        ]
        covered = [(a, b) for a, b in covered if b > a]
        values.append((done - due - union_s(covered)) * 1000.0)
    return quantile(values, q)

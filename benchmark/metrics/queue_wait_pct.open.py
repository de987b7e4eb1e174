"""Serving (host): for each request answered in the traced window, the
share of its time from due to answer that passed before the server
picked its batch up for dispatch, averaged over the requests. The wait
runs from the due time to the end of the server's own ``queue_wait``
span for the request (``ctx["server_spans"]``, on ``time.time_ns``,
put on the loop's clock by the request's own submit reading)."""


def read(ctx):
    picked = {rid: t1 for name, rid, _, t1 in ctx.get("server_spans", ())
              if name == "queue_wait" and rid is not None}
    shares = []
    for r in ctx.get("requests", ()):
        if r["t_ready"] is None or r["id"] not in picked:
            continue
        total = r["t_ready"] - r["t_due"]
        wait = (r["t_submit"] - r["t_due"]
                + (picked[r["id"]] - r["t_submit_ns"]) * 1e-9)
        if total > 0:
            shares.append(wait / total)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)

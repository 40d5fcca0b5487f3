"""Mean wait in the gateway's queue: ``admit_t - submit_t`` (the
gateway's own stamps) over the requests first given a lane inside the
window.  A program that does not stamp ``admit_t`` gives no value."""


def read(run):
    c = run.client
    waits = []
    for tr in c.tracked:
        admit = getattr(tr.req, "admit_t", None)
        if admit is not None and c.t0 <= admit < c.end:
            waits.append(admit - tr.req.submit_t)
    return 1e3 * sum(waits) / len(waits) if waits else None

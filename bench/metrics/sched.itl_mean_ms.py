"""Mean of the gaps that ``itl_p95_ms`` ranks: between consecutive output
tokens of one request, both delivered in the window, pooled over all
requests.  The cadence the scheduler gives a decoding lane (one step of
each tier group that holds lanes, and the prefill chunks between); it
moves with every gap, where the p95 moves only with those near it."""


def read(run):
    gaps = run.client.itl_ms()
    return sum(gaps) / len(gaps) if gaps else None

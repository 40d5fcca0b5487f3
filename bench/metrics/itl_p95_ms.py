"""95th percentile (nearest rank) of the gaps between consecutive output
tokens of one request, both delivered in the window, pooled over all
requests."""
from bench.client import nearest_rank


def read(run):
    gaps = run.client.itl_ms()
    return nearest_rank(gaps, 0.95) if gaps else None

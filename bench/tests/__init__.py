"""Tests of the benchmark harness (CPU, small sizes)."""

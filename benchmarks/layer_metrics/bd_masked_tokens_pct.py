"""Loss-bearing positions over real tokens of the rows the text plane noised in
the window (counters ``bd_positions_masked_total`` / ``bd_tokens_real_total``):
near 50 under t ~ U(1e-3, 1) a block."""

from benchmarks.layer_metrics import _program


def read(run):
    masked = _program.counter(run, "bd_positions_masked_total")
    real = _program.counter(run, "bd_tokens_real_total")
    return None if masked is None or not real else 100.0 * masked / real

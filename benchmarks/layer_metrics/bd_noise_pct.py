"""Seconds the text plane's producer spent drawing block-diffusion noise (span
``producer_noise``, counter ``data_producer_noise_seconds_total``) over the window.
The producer runs beside the loop: this is its thread's time, not the loop's."""

from benchmarks.layer_metrics import _program


def read(run):
    return _program.window_pct(run, "data_producer_noise_seconds_total")

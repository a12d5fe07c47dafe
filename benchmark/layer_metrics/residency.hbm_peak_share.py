"""The worker's peak device memory (the allocator's ``peak_bytes_in_use``
since process start: resident columns, program buffers, results) as a
share of one chip's HBM; the ``bench_window`` line's ``hbm_peak_share``
as a metric."""


def read(obs):
    peak = obs.device.get("memory_peak_bytes")
    if not peak or not obs.hbm_bytes:
        return None
    return 100.0 * peak / obs.hbm_bytes

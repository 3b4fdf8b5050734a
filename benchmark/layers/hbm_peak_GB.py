"""Layer "device": peak bytes in use on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``), in GB."""


def read(obs):
    peak = obs.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9

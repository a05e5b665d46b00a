"""Peak device memory of the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GB."""


def read(obs):
    peak = obs.device.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9

"""Mean live lanes over ``max_slots``, sampled from the engine's
``stats()`` four times a second during the traced window."""


def read(obs):
    samples = obs.observations.get("samples") or []
    if not samples:
        return None
    live = sum(s["live_slots"] for s in samples) / len(samples)
    return 100.0 * live / obs.observations["max_slots"]

"""100 * (1 - union of the device-op intervals / traced window)."""


def read(obs):
    t = obs["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s) if t.window_s > 0 else None

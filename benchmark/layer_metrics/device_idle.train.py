"""Device: share of the traced window in which no operation ran on the
chip (mean over the chips used)."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    if obs["trace"] is None or obs["trace"]["idle_share"] is None:
        return None
    return 100.0 * obs["trace"]["idle_share"]

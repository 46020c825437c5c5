"""Host feed: time the loop's thread is blocked in `next(staged)`, per step
(the benchmark's `feed.next` span; the change of epoch is inside it)."""
LAYER = "host feed"
UNIT = "ms/step"
SOURCE = "host_clock"
MOVES = "train_images_per_s"


def read(obs):
    span = obs["spans"].get("feed.next")
    steps = obs["counters"].get("steps")
    if not span or not steps:
        return None
    return span["seconds"] / steps * 1e3

"""Host feed: the share of the token slots of the rows the packer emitted
inside the window that hold a token (the program's counters
`data.pack.tokens` over `data.pack.slots`, handed over by the driver)."""

LAYER = "host feed"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_images_per_s"


def read(obs):
    slots = obs["counters"].get("pack_slots")
    if not slots or obs["shapes"].get("kind") != "lm_train":
        return None
    return 100.0 * obs["counters"].get("pack_tokens", 0) / slots

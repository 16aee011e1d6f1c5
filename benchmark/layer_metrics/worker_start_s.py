"""ray_tpu.init to the chip worker entering the benchmark's code (the
trainer's loop function or the replica's constructor): cluster start,
lease, worker process; jax start-up and model build excluded."""

LAYER = "cluster runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(view):
    return view["spans"].get("init_to_chip_worker")

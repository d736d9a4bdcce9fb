"""idle_share.engine: idle_share.train's reading (the device's idle share
of the traced window) in the engine cells, where it moves
engine_steps_per_s."""
from bench import readers

read = readers.same_as("idle_share.train")

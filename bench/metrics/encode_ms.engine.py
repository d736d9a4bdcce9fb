"""encode_ms.engine: encode_ms.train's reading (the stage.encode scope,
which the flat engines share with the trainer) in the engine cells, where
it moves engine_steps_per_s."""
from bench import readers

read = readers.same_as("encode_ms.train")

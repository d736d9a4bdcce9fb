"""apply_ms.engine: apply_ms.train's reading (the stage.apply scope,
which the flat engines share with the trainer) in the engine cells, where
it moves engine_steps_per_s."""
from bench import readers

read = readers.same_as("apply_ms.train")

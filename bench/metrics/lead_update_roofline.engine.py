"""lead_update_roofline.engine: lead_update_roofline.train's reading (the
same kernel, its bytes counted from the engine's shapes) in the engine
cells, where it moves engine_steps_per_s."""
from bench import readers

read = readers.same_as("lead_update_roofline.train")

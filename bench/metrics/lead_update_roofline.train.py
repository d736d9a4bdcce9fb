"""lead_update_roofline: kernels/lead_update.py's fused post-communication
LEAD update.  Memory-bound: 7 f32 reads and 4 f32 writes of the call's
rows (bench/counts.py lead_update_bytes) at 819 GB/s, over the summed time
of its events."""
from bench import readers

# the Pallas call as the trace shows it: a tpu_custom_call of 3 scalars and
# x, g, d, h, hw, q, Wq, giving the new x, d, h, hw
KERNEL = {"name": "lead_update", "target": "tpu_custom_call", "operands": 10,
          "results": ("f32", "f32", "f32", "f32")}


def read(ctx):
    return readers.roofline(ctx, KERNEL, "lead_update_bytes_per_step_per_chip",
                            "lead_update_calls_per_step_per_chip")

"""diff_encode_roofline.engine: kernels/lead_update.py's fused Y-difference
and 2-bit encode (lead_diff_encode).  Memory-bound: 5 f32 reads, an int8
code per element and an f32 scale per row (bench/counts.py
diff_encode_bytes) at 819 GB/s, over the summed time of its events."""
from bench import readers

# the Pallas call as the trace shows it: a tpu_custom_call of eta and
# x, g, d, h, dither, giving the int8 codes and the f32 scales
KERNEL = {"name": "lead_diff_encode", "target": "tpu_custom_call",
          "operands": 6, "results": ("s8", "f32")}


def read(ctx):
    return readers.roofline(ctx, KERNEL, "diff_encode_bytes_per_step_per_chip",
                            "diff_encode_calls_per_step_per_chip")

"""Names by which the reduction finds the program's step programs and
kernels in a device trace."""

# the engine jits functools.partial(model.mixed_step / decode_step): XLA
# names both modules jit__unknown; every other program of a step has a name
STEP_PROGRAMS = r"^jit__unknown"
# the paged attention kernel's custom call (kernels/paged_attention.py)
PAGED_KERNEL = r"^_paged_attention_pallas"

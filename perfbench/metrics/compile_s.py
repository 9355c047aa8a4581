"""Host seconds in the program engine's compile stage (its
``stage_timings_us["compile"]``), summed over the cell's programs: a
compile, or a load from JAX's persistent compilation cache."""


def read(run):
    us = run.stage_us.get("compile")
    return None if us is None else us / 1e6

"""Share of the window's build wall time in pass 2's spill: the build
report's pass2_spill phase (term ids, per-shard split, spill writes),
inside pass2_combine."""

from benchmark import program


def read(data):
    return program.build_share(data, ("pass2_spill",))

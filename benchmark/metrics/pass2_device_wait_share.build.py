"""Share of the window's build wall time that pass 2's host spent
waiting on device programs: the build report's pass2_device_wait phase
(each item's df fetch, then its shrink, which the depth-1 pipeline
queues behind the next item's group-by), inside pass2_combine."""

from benchmark import program


def read(data):
    return program.build_share(data, ("pass2_device_wait",))

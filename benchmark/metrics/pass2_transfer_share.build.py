"""Share of the window's build wall time in pass 2's transfers: the
build report's pass2_upload (padding and uploads) and pass2_fetch (the
shrunk pair columns' copy to the host) phases, inside pass2_combine."""

from benchmark import program


def read(data):
    return program.build_share(data, ("pass2_upload", "pass2_fetch"))

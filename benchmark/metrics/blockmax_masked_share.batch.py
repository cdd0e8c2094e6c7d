"""Share of the doc-block lanes the block-max kernels considered in the
traced window that their bounds masked: the capture's totals of the
program's `blockmax.blocks_masked` over `blockmax.blocks_considered`
counters. None where block-max did not engage."""

from benchmark import program


def read(data):
    if data["trace"] is None:
        return None
    considered = program.capture_count("blockmax.blocks_considered")
    if not considered:
        return None
    return 100.0 * (program.capture_count("blockmax.blocks_masked")
                    or 0) / considered

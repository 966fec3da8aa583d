"""Device busy time inside one run of the step program (the program with
most device time in the trace), median over the traced steps."""
from chipbench import readers


def read(obs):
    step = readers.program(obs, "total_s")
    return step and step[1]["median_s"] * 1e3

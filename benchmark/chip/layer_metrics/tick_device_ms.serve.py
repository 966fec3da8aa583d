"""Device busy time of one run of the decode-tick program (the program that
ran most often in the trace), median."""
from chipbench import readers


def read(obs):
    tick = readers.program(obs, "count")
    return tick and tick[1]["median_s"] * 1e3

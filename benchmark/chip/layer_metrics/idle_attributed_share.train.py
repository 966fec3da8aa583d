"""The share of the lead device's idle-gap seconds that lie under any
``mxtpu:`` span of the program, in percent: what of the idle time the
program's own spans name."""
from chipbench import program_spans


def read(obs):
    return program_spans.metric(obs, "idle_attributed_share")

"""Share of device busy time that falls under none of the program's
kernel scopes, even through the instructions around it
(``harness/trace_owners.py``)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.unnamed_share(obs)

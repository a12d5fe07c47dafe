"""Share of the window's CSR-expanded joins whose KEPT program maps its
output slots to probe rows in ONE pass (a scatter at probe length and a
running sum at output length) and not by a binary search per output slot
(``executor/device_join._expand_rows``;
``device_join.expand_one_pass(cap, n_probe)`` names the side from the
program's two static shapes alone: the search costs cap x
ceil(log2(n_probe + 1)) dependent gathers): growth of
``device_pipelines.join_expand_one_pass`` over the growth of
``device_pipelines.join_expand`` (both one per expanded join of a
dispatched fragment, ``DIAG STATUS``).  TPC-H Q13's 2,097,152 slots over
the 185,364-row customer bucket take the pass.  None on a program without the counter,
and where nothing expanded."""


def read(obs):
    try:
        one_pass = obs.counter_delta("device_pipelines",
                                     "join_expand_one_pass")
        expanded = obs.counter_delta("device_pipelines", "join_expand")
    except KeyError:       # a program without the counter
        return None
    return 100.0 * one_pass / expanded if expanded else None

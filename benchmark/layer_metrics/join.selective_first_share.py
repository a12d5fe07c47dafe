"""Share of the window's fact-first join chains that attached a build
whose filter lets the probe path cut ahead of a smaller candidate:
growth of ``device_pipelines.join_chains_selective`` over the growth of
``join_chains`` (``DIAG STATUS``; both count one per dispatched join
fragment whose inner joins ``device_join._reorder_fact_first`` chained,
``device_exec.note_join_chain``).  Such a build keeps at most a quarter
of its rows under its filter (``device_join._attach_rank``), so the cut
past its join comes after one lookup at the fact's length; a chain
whose builds do not cut keeps the size order.  100 where every chain of
the window took a cutting build first (SSB Q2.1-Q4.1 at SF10), 50 in a
mix where one template of two does (Q5 beside Q3, Q9 beside Q18's outer
fragment).  None on a program without the counters, and where no chain
was built."""


def read(obs):
    try:
        selective = obs.counter_delta("device_pipelines",
                                      "join_chains_selective")
        chains = obs.counter_delta("device_pipelines", "join_chains")
    except KeyError:       # a program without the counters
        return None
    return 100.0 * selective / chains if chains else None

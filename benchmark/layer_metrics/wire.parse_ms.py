"""Parser time per client statement over the window: growth of
``server.parse_s`` over growth of ``server.statements`` (``DIAG STATUS``).
Parsing runs before the statement's trace begins, so it has a counter
where the rest has spans; it is part of ``wire.outside_worker_ms``."""


def read(obs):
    try:
        n = obs.counter_delta("server", "statements")
        return 1e3 * obs.counter_delta("server", "parse_s") / n if n \
            else None
    except KeyError:       # a program without the counter
        return None

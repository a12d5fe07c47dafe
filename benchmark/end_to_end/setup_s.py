"""Process start to the first request of the window: worker boot, data,
reference answers, warm-up passes, one EXPLAIN ANALYZE per template."""


def read(obs):
    return obs.setup["setup_s"]

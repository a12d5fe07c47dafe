"""Share of the window's join fragments whose probe leaf was read from
columns resident in HBM through the residency ledger (whole, or page by
page as slices cut on the device), and not from pages the statement cut
from the host's columns and sent: growth of
``device_pipelines.join_probe_resident`` over the growth of both
``join_probe_*`` counters (``DIAG STATUS``; one count per dispatched join
fragment, capacity restarts once; the mesh and the hybrid join count
under neither).  A deployment that states "resident in HBM" reads 100."""


def read(obs):
    try:
        resident = obs.counter_delta("device_pipelines",
                                     "join_probe_resident")
        sent = obs.counter_delta("device_pipelines", "join_probe_sent")
    except KeyError:       # a program without the counters
        return None
    return 100.0 * resident / (resident + sent) if resident + sent else None

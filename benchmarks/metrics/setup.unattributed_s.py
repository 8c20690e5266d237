"""Set-up no span of the program explains: ``setup_s`` less the seconds of it
that at least one span of the three processes covers, the warm-up's
``train_window`` and ``host_sync`` among them, overlaps counted once. What is
left: the interpreters' starts and imports, and the benchmark's own work
before the window (the compiled step's text, the comparison's programs).
Nothing unless both ends of the chain were collected: the launcher's
``device_probe`` and the worker's ``backend_init``."""

from benchmarks import setup_reduce


def read(run: dict):
    records = setup_reduce.spans(run)
    if not (setup_reduce.find(records, "device_probe")
            and setup_reduce.find(records, "backend_init")):
        return None
    start, opened = run["started_wall"], run["window"]["opened_wall"]
    return (opened - start) - setup_reduce.covered(records, start, opened)

"""Share of the window the loop spent inside the loader's own ``next()``
(a host wait, on the host clock, taken by the feed from outside)."""


def read(run: dict):
    window = run["window"]
    waited = sum(c["fetch_to"] - c["fetch_from"] for c in window["calls"])
    if not window["calls"]:
        return None
    return 100.0 * waited / window["seconds"]

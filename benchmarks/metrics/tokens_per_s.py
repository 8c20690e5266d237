"""Tokens trained in the window over the window's seconds: every step the
feed handed out, over the time from the window's opening to the returned
state being ready on the device (stalls and commit waits included)."""


def read(run: dict):
    window = run["window"]
    if not window["steps"]:
        return None
    return window["steps"] * window["tokens_per_step"] / window["seconds"]

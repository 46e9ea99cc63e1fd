"""Marker files: how the processes of a served cell tell each other when
to start, trace and stop. A marker appears whole (written beside, then
renamed) and may carry a line of text."""

import os
import time


def put(path: str, text: str = "") -> None:
    with open(path + ".tmp", "w") as f:
        f.write(text)
    os.replace(path + ".tmp", path)


def wait(path: str, poll_s: float = 0.01) -> str:
    """Block until the marker is there -> its text."""
    while not os.path.exists(path):
        time.sleep(poll_s)
    with open(path) as f:
        return f.read()

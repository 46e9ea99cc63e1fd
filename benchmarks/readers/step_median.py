"""Median over the window's steps of one host-clock field of a step."""

import statistics


def read(observed: dict, params: dict):
    values = [s[params["field"]] for s in observed.get("steps", ())
              if params["field"] in s]
    return statistics.median(values) if values else None

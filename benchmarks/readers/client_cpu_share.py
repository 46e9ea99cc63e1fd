"""CPU seconds the client processes used inside the window over window x
processes: how close the load generator is to being the bottleneck."""


def read(observed: dict, params: dict):
    c = observed.get("clients")
    if not c or not c["window_s"] or not c["processes"]:
        return None
    return 100.0 * c["cpu_s"] / (c["window_s"] * c["processes"])

"""Share of the device's busy time inside Mosaic custom calls, all kernels
together (the program names none of them yet)."""


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["mosaic_s"] / trace["busy_s"]

"""Share of the messages due in the window that were given a correct
verdict on the device path inside it (``sets_in_window`` over ``due``):
the share of the offered load the service kept up with."""


def read(run):
    c = run["counters"]
    if not c.get("due"):
        return None
    return 100.0 * c["sets_in_window"] / c["due"]

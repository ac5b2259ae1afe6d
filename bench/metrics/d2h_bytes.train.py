"""Bytes each file checkpoint moved off the device, as the program
counts them (`FileCheckpointer.last_write["d2h_bytes"]`)."""


def read(view):
    b = view["records"]["d2h_bytes"]
    return sum(b) / len(b) if b else None

"""The Pallas digest kernel's share of its roofline, in percent: the
bytes handed to it in the traced window over the HBM peak (it reads each
byte once and is bound by bandwidth), over the device seconds of its
jitted entry `checksum_kernel` (which pads the words and sums the lanes
around the Pallas call)."""
from bench.trace import programs_matching


def read(view):
    nbytes = view["records"]["digested_bytes"]
    runs, secs = programs_matching(view["trace"], r"^jit_checksum_kernel$")
    if not nbytes or not secs:
        return None
    secs /= view["trace"]["devices"]
    return 100 * nbytes / view["peaks"]["hbm_bytes_per_s"] / secs

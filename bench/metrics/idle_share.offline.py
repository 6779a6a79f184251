"""Share of the offline window in which no operation ran on the device
(1 - union of busy intervals / window, averaged over the chips)."""
from bench import tracereduce


def read(ctx):
    """The idle share in %, from the window's device trace."""
    return 100.0 * tracereduce.idle_share(ctx["busy"], ctx["lo"], ctx["hi"])

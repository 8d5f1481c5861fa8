"""Mean share of the decode slots occupied per engine step in the window
(the engine's ``occupancy`` list), in percent."""

import numpy as np


def read(record):
    occ = record.get("occupancy")
    return 100.0 * float(np.mean(occ)) if occ else None

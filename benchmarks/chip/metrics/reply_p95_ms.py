"""95th percentile, over every upload ingested in the window, of the time
from the start of the superstep that collected it to the return of the
ingest that took it in."""
import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.replies), 95)) * 1e3

"""Device nanoseconds of the sweep program per lane-job per worker
(``metrics._shared.lane_ns_per_elem``), in the cells whose rate is
``table_lanejobs_per_s``."""
from metrics._shared import lane_ns_per_elem as read  # noqa: F401

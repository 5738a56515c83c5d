"""Plain reference for `statements/ssb_q2_2.sql` (SSB Q2.2): its
description in `references/ssb.py`, whose one function answers all
thirteen."""

from .ssb import reference_for

build, compare, tolerance = reference_for("q2_2")

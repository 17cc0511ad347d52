"""Pricing of the CiM run per design point: self-time of ``price.cim``
(the reshaped trace) and ``price.macr`` (the MACR breakdown), in ms.
Moves ``points_per_s``."""


def read(m):
    return m.ms_per_point("price.cim", "price.macr")

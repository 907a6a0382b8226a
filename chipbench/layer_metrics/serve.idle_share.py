"""Share of the traced slice in which no operation ran on the chip, in a
serving cell.  The same number as device.idle_share; a metric names one
end-to-end metric it moves, and here that is the gap between tokens."""

from chipbench.readers import idle_share as read  # noqa: F401

"""1 - (union of device-operation intervals / traced slice), mean over the
chips used."""

from chipbench.readers import idle_share as read  # noqa: F401

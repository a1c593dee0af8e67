"""Host runtime: ZMW selection, logging, chemistry."""

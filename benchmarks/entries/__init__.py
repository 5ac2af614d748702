"""One module per way of building and driving a system under test, found by
the name a configuration file gives under ``entries``."""

"""One module per family and kind of traffic, ``<family>_<kind>.py``: what
builds and drives that family's system under test (``spec.Cell.entry``)."""

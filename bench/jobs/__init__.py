"""Job drivers: one per kind of traffic file (``"kind"``)."""

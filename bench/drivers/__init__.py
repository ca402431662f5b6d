"""Cell drivers: one module per kind of traffic (``traffic[].driver``)."""

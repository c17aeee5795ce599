"""One module per architecture, found by the configuration's ``model_type``
(``harness.arch``)."""

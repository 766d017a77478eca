"""One module per split net, named by a configuration's ``"model"`` key
(``spec.load_model``)."""

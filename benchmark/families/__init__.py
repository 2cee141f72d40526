"""Model families: how a configuration file's published keys become the
program's model config and its weights. A configuration names one under
``family``; the module has ``model_config(config)`` and
``init_params(model_config, key)``. A new family is a new module here."""

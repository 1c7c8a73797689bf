"""Plain references of the model configurations, one module per
architecture, named by the ``reference`` key of a configuration file."""

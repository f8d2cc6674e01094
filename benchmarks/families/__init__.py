"""One module per model family: how a configuration file becomes the
repository's model, what a batch of it looks like, how many operations a
token requires, and which plain reference judges it. Found by the
``family`` key of the configuration file."""

"""One module per kind of window; a traffic file names its ``driver``."""

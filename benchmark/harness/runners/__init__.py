"""One file per runner kind; a traffic mix names its runner."""

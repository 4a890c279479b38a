"""One file per system under test, named by a configuration's ``system``."""

"""Traffic generators, one per kind of work, named by a mix's ``driver``."""

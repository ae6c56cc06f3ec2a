"""Plain references, written from the equations in numpy float32. Nothing
here imports the program (`adapm_tpu`) or takes anything it has made."""

"""Plain references the checks compare the program with.  They import
nothing of the program and take nothing it made."""

"""Plain references that decide ``correct``. They import nothing of the
program and take nothing it made beyond the answers under check and
the fitted model those answers are over."""

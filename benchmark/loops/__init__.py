"""Traffic loops, one module per ``loop`` a traffic file names."""

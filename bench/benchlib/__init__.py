"""The chip benchmark's library: discovery and the run (``harness``), the
client (``loop``), traffic generation shared by the generator files
(``gen``), tenants and server (``deploy``), the plain reference
(``reference``), byte layouts (``wire``), trace reduction (``tracefile``)
and needed work with the peak table (``work``)."""

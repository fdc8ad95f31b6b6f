# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

# The names flash_attention.py gives its forward's output and log-sum-exp,
# which a rematerialised block keeps (models/model.py): defined here so that
# naming them imports no kernel and no Pallas.
FLASH_RESIDUALS = ("flash_attention.o", "flash_attention.lse")

"""What every fluid training family hands the driver: the compiled step
with its state, one object, built once, driven through its first steps by
set-up and handed to the window."""


class FluidStep:
    def __init__(self, main, startup, loss, scope, names, feed_names=None):
        """``names``: reference leaf (``layers.q_w[3]``) -> the program's
        parameter variable. ``feed_names``: the family's feed key -> the
        program's feed variable, where they differ."""
        import paddle_tpu.fluid as fluid

        self.fluid = fluid
        self.main, self.startup, self.loss = main, startup, loss
        self.scope, self.names, self.feed_names = scope, names, feed_names
        self.exe = fluid.Executor()
        self.reset()
        moment1 = {op.input("Param")[0]: op.input("Moment1")[0]
                   for op in main.global_block().ops if op.type == "adam"}
        assert set(moment1) == set(names.values()), (
            "the optimizer's parameters and the reference's leaves differ: "
            "%r" % sorted(set(moment1) ^ set(names.values())))
        self.moment1_names = {k: moment1[v] for k, v in names.items()}

    def reset(self):
        """Optimizer state (and parameters) as the startup program leaves
        them; the readings tool drives many seeds through one step."""
        with self.fluid.scope_guard(self.scope):
            self.exe.run(self.startup)

    def set_params(self, flat):
        for k, name in self.names.items():
            old = self.scope.find_var(name)
            assert tuple(old.shape) == tuple(flat[k].shape), (
                name, old.shape, flat[k].shape)
            self.scope.set_var(name, flat[k])

    def run(self, feed):
        """One training step; the loss stays on the device."""
        if self.feed_names:
            feed = {self.feed_names[k]: v for k, v in feed.items()}
        with self.fluid.scope_guard(self.scope):
            (lv,) = self.exe.run(self.main, feed=feed,
                                 fetch_list=[self.loss], return_numpy=False)
        return lv

    def params(self):
        return {k: self.scope.find_var(n) for k, n in self.names.items()}

    def first_moments(self):
        return {k: self.scope.find_var(n)
                for k, n in self.moment1_names.items()}

    def free(self):
        for name in list(self.scope.var_names()):
            self.scope.erase(name)

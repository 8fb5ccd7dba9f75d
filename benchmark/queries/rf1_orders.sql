insert into orders
select o_orderkey + (select max(o_orderkey) from orders),
       o_custkey, o_orderstatus, o_totalprice, o_orderdate, o_orderpriority,
       o_clerk, o_shippriority, o_comment
from orders
where o_orderkey between (select min(o_orderkey) from orders)
                     and (select min(o_orderkey) + 7499 from orders)
